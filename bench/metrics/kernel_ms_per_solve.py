"""Device time of the Mosaic kernels per solve, in ms; the worst device."""


def read(r):
    per = [d.mosaic_ns / d.solves * 1e-6 for d in r.devices
           if d.solves and d.mosaic_ns > 0]
    return max(per) if per else None
