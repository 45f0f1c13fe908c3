"""Device time of every op other than the Mosaic kernels inside the solve
program (the lane layout around the kernel), per solve, in ms; the worst
device.  Solves are the host's count of the window."""


def read(r):
    per = [d.layout_ns / r.solves * 1e-6 for d in r.devices if d.solves]
    return max(per) if per else None
