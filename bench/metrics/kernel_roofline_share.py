"""Algorithmic vector operations per second of kernel time over the device's
measured f32 vector peak, in %; the worst device.

Operations are the configuration's count per attempted lane-step times the
lane-steps the device's trajectories attempted (padded lanes and masked
lockstep steps count nothing), plus its count per save times the saves,
once per solve the host counted in the window."""


def read(r):
    if r.peak is None:
        return None
    w = r.work
    shares = []
    for d, attempts, lanes in zip(r.devices, r.attempts, r.lanes):
        if not d.solves or d.mosaic_ns <= 0:
            continue
        ops = r.solves * (w["ops_per_attempt"] * attempts
                          + w["ops_per_save"] * w["saves"] * lanes)
        rate = ops / (d.mosaic_ns * 1e-9)
        shares.append(100.0 * rate / r.peak["vector_ops_per_s"])
    return min(shares) if shares else None
