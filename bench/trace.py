"""Reduction of a profiler trace (`.xplane.pb`) to the benchmark's device numbers.

A TPU trace holds one plane per device (``/device:TPU:<k>``) whose line
"XLA Ops" has one event per executed HLO op and whose line "XLA Modules"
has one event per executed program, and host planes whose events include
the benchmark's `jax.profiler.TraceAnnotation` spans.  Everything is read
on one clock, in nanoseconds.

For each device this gives: the busy time (the union of its op intervals
inside the window), the time of the Mosaic kernels (ops whose HLO is a
``tpu_custom_call``), the time of every other op inside the executions of
the benchmark's solve program (the lane layout around the kernel), the
number of those executions, the time per op name, and the idle gaps.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)
MOSAIC_MARK = 'custom_call_target="tpu_custom_call"'
DEVICE_PREFIX = "/device:TPU:"


@dataclasses.dataclass
class Event:
    start: float
    end: float
    name: str


@dataclasses.dataclass
class Trace:
    devices: Dict[str, Dict[str, List[Event]]]   # plane -> line -> events
    host: List[Event]


@dataclasses.dataclass
class DeviceStats:
    device: str
    busy_ns: float
    mosaic_ns: float
    layout_ns: float
    solves: int
    op_ns: Dict[str, float]
    gaps: List[Interval]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in pd.planes:
        lines = {ln.name: [Event(e.start_ns, e.start_ns + e.duration_ns,
                                 e.name) for e in ln.events]
                 for ln in plane.lines}
        if plane.name.startswith(DEVICE_PREFIX):
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for evs in lines.values():
                host.extend(evs)
    return Trace(devices=dict(sorted(devices.items(),
                                     key=lambda kv: _device_index(kv[0]))),
                 host=host)


def _device_index(plane: str) -> int:
    return int(plane[len(DEVICE_PREFIX):])


def merge(intervals: List[Interval]) -> List[Interval]:
    """Union of intervals as sorted, disjoint intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals: List[Interval]) -> float:
    return float(sum(e - s for s, e in intervals))


def short_name(op: str) -> str:
    """'%fusion.3 = f32[...] fusion(...)' -> 'fusion.3'."""
    head = op.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def is_mosaic(op: str) -> bool:
    return MOSAIC_MARK in op


def find_span(trace: Trace, name: str) -> Optional[Interval]:
    """The first host span of this name, as (start_ns, end_ns)."""
    for ev in sorted(trace.host, key=lambda e: e.start):
        if ev.name == name:
            return ev.start, ev.end
    return None


def reduce_device(plane: str, lines: Dict[str, List[Event]],
                  window: Interval, module_prefix: str) -> DeviceStats:
    lo, hi = window
    ops = [e for e in lines.get("XLA Ops", []) if e.end > lo and e.start < hi]
    busy = merge(clip([(e.start, e.end) for e in ops], lo, hi))
    # a solve program belongs to the window where it overlaps it: the
    # host's and the device's clocks agree only to some microseconds.
    # Each execution counts once, also where two touch back to back.
    runs = [(e.start, e.end) for e in lines.get("XLA Modules", [])
            if e.name.startswith(module_prefix)
            and e.end > lo and e.start < hi]
    solves = merge(runs)
    mosaic = layout = 0.0
    op_ns: Dict[str, float] = {}
    for e in ops:
        d = min(e.end, hi) - max(e.start, lo)
        key = short_name(e.name) + (" (mosaic)" if is_mosaic(e.name) else "")
        op_ns[key] = op_ns.get(key, 0.0) + d
        if is_mosaic(e.name):
            mosaic += d
        elif any(a <= e.start and e.end <= b for a, b in solves):
            layout += d
    gaps = [(a[1], b[0]) for a, b in zip([(lo, lo)] + busy, busy + [(hi, hi)])
            if b[0] > a[1]]
    return DeviceStats(device=plane, busy_ns=length(busy), mosaic_ns=mosaic,
                       layout_ns=layout, solves=len(runs), op_ns=op_ns,
                       gaps=gaps)


def reduce(trace: Trace, window: Interval,
           module_prefix: str) -> List[DeviceStats]:
    return [reduce_device(p, lines, window, module_prefix)
            for p, lines in trace.devices.items()]


def check_solves(stats: List[DeviceStats], solves: int) -> None:
    """Every device ran the solve program as often as the host counted
    solves in the window; the per-solve readers divide by the host's count."""
    for d in stats:
        if d.solves != solves:
            raise RuntimeError(
                f"{d.device} ran the solve program {d.solves} times in the "
                f"traced window; the host counted {solves} solves")


def name_gap(trace: Trace, gap: Interval, window: str = "bench_window") -> str:
    """What the host was doing in an idle gap: the shortest host span that
    covers the gap's middle, other than the window's own, or 'host: no
    span'."""
    mid = 0.5 * (gap[0] + gap[1])
    best = None
    for ev in trace.host:
        if ev.start <= mid <= ev.end and ev.end > ev.start \
                and ev.name != window:
            if best is None or ev.end - ev.start < best.end - best.start:
                best = ev
    return best.name if best is not None else "host: no span"
