"""Per-layer metric readers, one module per metric, found by its name.

Each module has ``read(reading) -> float | None``.  `reading` carries the
reduced trace of the window (`bench.trace.DeviceStats` per device), the
window's length, the solve count, per-device attempted lane-steps, the
ensemble's RHS count, the configuration's work counts and the device
peaks.  A reader that finds nothing to read returns None, and the metric
is left out of the result line.
"""
