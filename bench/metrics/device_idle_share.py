"""Share of the traced window in which no operation ran on the device, in %;
the worst device on several chips."""


def read(r):
    if not r.devices or r.window_ns <= 0:
        return None
    return max(100.0 * (1.0 - d.busy_ns / r.window_ns) for d in r.devices)
