"""The trace reduction: intervals, op categories, per-solve counts and idle
gaps, on hand-made events and on a small trace recorded on a TPU v5 lite."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import trace as tr
from bench.metrics import (device_idle_share, kernel_ms_per_solve,
                           kernel_roofline_share, layout_ms_per_solve)

FIXTURE = Path(__file__).with_name("fixtures") / "lorenz_fixed_2e16.xplane.pb"
KERNEL = ('%bench_solve.1 = (f32[4,3,8]) custom-call(f32[3,8] %a), '
          'custom_call_target="tpu_custom_call"')
COPY = "%copy = f32[8,4,3] copy(f32[8,4,3] %b)"
OTHER = "%fusion.7 = f32[4] fusion()"


def _ev(s, e, name):
    return tr.Event(float(s), float(e), name)


def _hand_trace():
    # window [0, 100); two solve programs [10, 40) and [50, 90) with a
    # kernel and a copy each; one op outside any solve program; host spans
    lines = {
        "XLA Modules": [_ev(10, 40, "jit_bench_solve(1)"),
                        _ev(50, 90, "jit_bench_solve(1)"),
                        _ev(92, 96, "jit_other(2)")],
        "XLA Ops": [_ev(11, 35, KERNEL), _ev(35, 39, COPY),
                    _ev(51, 80, KERNEL), _ev(80, 88, COPY),
                    _ev(92, 96, OTHER)],
    }
    host = [_ev(0, 100, "bench_window"), _ev(40, 50, "PjitFunction(x)"),
            _ev(0, 200, "outer")]
    return tr.Trace(devices={"/device:TPU:0": lines}, host=host)


def test_merge_clip_length():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert tr.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]
    assert tr.length([(0, 3), (5, 9)]) == 7


def test_names_and_categories():
    assert tr.short_name(KERNEL) == "bench_solve.1"
    assert tr.is_mosaic(KERNEL) and not tr.is_mosaic(COPY)


def test_reduce_by_hand():
    t = _hand_trace()
    window = tr.find_span(t, "bench_window")
    assert window == (0.0, 100.0)
    (d,) = tr.reduce(t, window, "jit_bench_solve")
    # busy: [11,39) + [51,88) + [92,96) = 28 + 37 + 4
    assert d.busy_ns == 69.0
    assert d.mosaic_ns == 24.0 + 29.0
    assert d.layout_ns == 4.0 + 8.0          # the copies; fusion.7 is outside
    assert d.solves == 2
    assert d.op_ns == {"bench_solve.1 (mosaic)": 53.0, "copy": 12.0,
                       "fusion.7": 4.0}
    assert d.gaps == [(0.0, 11.0), (39.0, 51.0), (88.0, 92.0), (96.0, 100.0)]
    assert tr.name_gap(t, (39.0, 51.0)) == "PjitFunction(x)"
    assert tr.name_gap(t, (0.0, 11.0)) == "outer"      # not the window


def _reading(stats, window_ns, attempts, lanes, peak=1e9):
    return SimpleNamespace(
        devices=stats, window_ns=window_ns, attempts=attempts, lanes=lanes,
        work=dict(ops_per_attempt=10, ops_per_save=3, saves=2),
        peak={"vector_ops_per_s": peak}, nf=0, n=sum(lanes))


def test_readers_by_hand():
    t = _hand_trace()
    stats = tr.reduce(t, (0.0, 100.0), "jit_bench_solve")
    r = _reading(stats, 100.0, attempts=[1000], lanes=[4])
    assert device_idle_share.read(r) == pytest.approx(31.0)
    assert kernel_ms_per_solve.read(r) == pytest.approx(26.5e-6)
    assert layout_ms_per_solve.read(r) == pytest.approx(6e-6)
    # 2 solves x (10 x 1000 + 3 x 2 x 4) ops over 53 ns, against 1e9 op/s
    ops = 2 * (10 * 1000 + 3 * 2 * 4)
    assert kernel_roofline_share.read(r) == pytest.approx(
        100 * ops / 53e-9 / 1e9)


def test_readers_find_nothing():
    empty = tr.DeviceStats("/device:TPU:0", 0.0, 0.0, 0.0, 0, {}, [])
    r = _reading([empty], 100.0, attempts=[0], lanes=[4])
    assert kernel_ms_per_solve.read(r) is None
    assert layout_ms_per_solve.read(r) is None
    assert kernel_roofline_share.read(r) is None
    r.peak = None
    assert kernel_roofline_share.read(r) is None


# The fixture: `bench/run.py`'s traced window of lorenz_fixed at 2^16
# trajectories, six solves, recorded on one TPU v5 lite.  Its device op
# listing, by hand: per solve a constant `fusion.1` (8 ns), the Mosaic
# kernel, a `slice_reduce_fusion` (~2.27 us) and a `copy` of the saves into
# trajectory-major order (~7.4 us), back to back inside one execution of
# `jit_bench_solve`.
FIXTURE_KERNELS = [(42954181, 91535093), (93045071, 141625983),
                   (143045076, 191626218), (193022352, 241603263),
                   (243224828, 291805739), (293930463, 342511375)]
FIXTURE_WINDOW = (42503609.0, 343553969.0)


@pytest.fixture(scope="module")
def recorded():
    return tr.load(str(FIXTURE))


def test_fixture_window_and_solves(recorded):
    assert tr.find_span(recorded, "bench_window") == FIXTURE_WINDOW
    (d,) = tr.reduce(recorded, FIXTURE_WINDOW, "jit_bench_solve")
    assert d.device == "/device:TPU:0"
    assert d.solves == 6


def test_fixture_categories(recorded):
    (d,) = tr.reduce(recorded, FIXTURE_WINDOW, "jit_bench_solve")
    assert d.mosaic_ns == sum(b - a for a, b in FIXTURE_KERNELS)
    assert d.mosaic_ns == 291485700.0
    # six fusion.1 of 8 ns, six slice_reduce_fusion, six copies
    assert d.op_ns["fusion.1"] == 48.0
    assert d.op_ns["slice_reduce_fusion"] == 13626.0
    assert d.op_ns["copy"] == 44321.0
    assert d.layout_ns == 48.0 + 13626.0 + 44321.0
    # no two ops overlap: busy is the kernels plus the layout ops
    assert d.busy_ns == d.mosaic_ns + d.layout_ns


def test_fixture_idle_and_per_solve(recorded):
    stats = tr.reduce(recorded, FIXTURE_WINDOW, "jit_bench_solve")
    window = FIXTURE_WINDOW[1] - FIXTURE_WINDOW[0]
    r = _reading(stats, window, attempts=[1000 * 65536], lanes=[65536])
    assert device_idle_share.read(r) == pytest.approx(
        100 * (1 - 291543695.0 / 301050360.0))
    assert kernel_ms_per_solve.read(r) == pytest.approx(291.4857 / 6)
    assert layout_ms_per_solve.read(r) == pytest.approx(0.057995 / 6)
    (d,) = stats
    # gaps: before the first op, 5 between solves plus the small ones
    # between ops inside a solve, and after the last op
    assert d.gaps[0] == (FIXTURE_WINDOW[0], 42954171.0)
    assert d.gaps[-1] == (342521007.0, FIXTURE_WINDOW[1])
    assert tr.length(d.gaps) == pytest.approx(window - d.busy_ns)
