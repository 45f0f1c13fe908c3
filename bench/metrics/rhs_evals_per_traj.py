"""Right-hand-side evaluations per trajectory of one solve, from the
ensemble result's ``nf`` (the same count on every solve)."""


def read(r):
    return r.nf / r.n if r.n else None
