"""One run of one benchmark cell: set-up, the measured window, checks, result.

Everything is found by name from `BENCHMARK.json`: the cell names its
configuration (module and JSON file under `bench/configs/`) and its traffic
(`bench/traffic/<traffic>.json`); each per-layer metric is a reader
`bench/metrics/<name>.py`.  Adding a cell, a mix or a metric adds files
and entries and edits none of this.

A run:

1. Set-up (``setup_s``, from process start): imports, device start, the
   persistent compilation cache, inputs made on the device from the seed,
   the solve compiled (its TPU kernel checked) and run once untimed;
   last, the objects set-up left are collected and frozen, so that no
   collection inside the window walks them.
2. Window (`time_window`): solves two deep, each dispatched before the
   one ahead of it is waited on, so one always stands queued behind the
   running one; each is counted once its ``block_until_ready`` returns.
   Once one ends after ``seconds`` nothing more is dispatched, and the
   window ends with the queued one.  Compilations inside are counted.
3. Checks, after the window and the memory reading, on the last solve.
4. With ``trace``: the window runs under the profiler, the vector peak is
   measured, and the per-layer readers take their numbers from the trace.
"""
from __future__ import annotations

import gc
import importlib
import json
import os
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
SOLVE_NAME = "bench_solve"
TRACE_DIR = ROOT / ".bench_trace"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --- finding things by name -----------------------------------------------------

def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(spec: dict, workload: str):
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    return cell, config


def load_config(name: str):
    return importlib.import_module(f"bench.configs.{name}")


def load_traffic(name: str, root: Path = ROOT) -> dict:
    return json.loads((root / "bench" / "traffic" / f"{name}.json")
                      .read_text())


def load_metric(name: str):
    return importlib.import_module(f"bench.metrics.{name}")


def metrics_for(spec: dict, kind: str, workload: str) -> list:
    return [m for m in spec[kind]
            if workload in m.get("workloads", [workload])]


def key_words(seed: int) -> np.ndarray:
    """Two uint32 words from any whole-number seed."""
    entropy = int(seed) % (1 << 64)
    return np.random.SeedSequence(entropy).generate_state(2, np.uint32)


# --- the compilation cache and compile counting ----------------------------------

def use_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent cache at a fixed path: `JAX_COMPILATION_CACHE_DIR`
    where set (JAX reads it itself), else `<checkout>/.jax_cache`.  Every
    program is cached, however quickly it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts JAX's compile events (backend compiles and cache loads)
    while entered."""

    def __init__(self):
        self.count = 0
        self._on = False

    def _listener(self, event, duration, **kwargs):
        if self._on and ("/compile/backend_compile" in event
                         or "cache_retrieval" in event):
            self.count += 1

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._listener)
        self._on = True
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        self._on = False
        jax.monitoring.unregister_event_duration_listener(self._listener)


# --- device facts -----------------------------------------------------------------

def device_info(devices) -> dict:
    d = devices[0]
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def lane_blocks(values, chips: int) -> list:
    """Sums of a per-lane vector over each device's contiguous shard."""
    v = np.asarray(values, np.int64)
    return [int(b.sum()) for b in np.array_split(v, chips)]


# --- the run ----------------------------------------------------------------------

def setup_cell(workload: str, n: int = None) -> SimpleNamespace:
    """What a run of the cell needs before its inputs: the cell, its
    configuration module and traffic, the ensemble size, and on several
    chips the mesh and the lane sharding."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = load_spec()
    cell, _ = find_cell(spec, workload)
    traffic = load_traffic(cell["traffic"])
    c = SimpleNamespace(spec=spec, cell=cell, cfg=load_config(cell["config"]),
                        traffic=traffic, n=n or traffic["n_traj"],
                        chips=cell["chips"], mesh=None, sharding=None,
                        devices=jax.devices()[:cell["chips"]])
    if c.chips > 1:
        from repro.launch.mesh import make_local_mesh
        c.mesh = make_local_mesh()
        c.sharding = NamedSharding(c.mesh, P(c.mesh.axis_names[0]))
        c.devices = list(c.mesh.devices.flat)
    return c


def make_inputs(c: SimpleNamespace, seed: int):
    import jax.numpy as jnp
    return c.cfg.make_inputs(jnp.asarray(key_words(seed)), c.n, c.sharding)


def check(c: SimpleNamespace, out, inputs, seed: int) -> list:
    """[(name, value, limit)] of the configuration's compared numbers."""
    return [(name, float(value), float(c.traffic["limits"][name]))
            for name, value in c.cfg.check(out, inputs, c.traffic, seed)]


def time_window(dispatch, wait, seconds: float, clock=time.perf_counter):
    """Run solves two deep for at least `seconds`.

    `dispatch()` enqueues a solve and returns its output; `wait(out)`
    returns once that solve has ended.  Solve k+1 is dispatched before
    solve k is waited on, so the device runs on through the host's sync
    after each solve, and never more than one solve waits in the queue.
    Once a solve ends after `seconds`, the one still queued is waited on
    and counted, and the window ends with it: every solve dispatched is
    counted.  Returns (last output, solves, t_start, t_end)."""
    t_start = clock()
    running = dispatch()
    solves = 0
    while True:
        queued = dispatch()
        wait(running)
        solves += 1
        running = queued
        if clock() - t_start >= seconds:
            break
    wait(running)
    return running, solves + 1, t_start, clock()


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, rehearse_n: int = None, fault=None) -> dict:
    """Run one cell and return its result object (see `bench/run.py`).

    `rehearse_n` shrinks the ensemble for a CPU rehearsal (Pallas in
    interpret mode).  `fault(solve, n)` wraps the timed solve, for the
    tests that break the timed path on purpose."""
    import jax

    from bench.checks import holds_mosaic_kernel

    marks = [("start", time.perf_counter())]
    c = setup_cell(workload, rehearse_n)
    spec, cfg, traffic, n, chips = c.spec, c.cfg, c.traffic, c.n, c.chips
    devices = c.devices
    on_tpu = devices[0].platform == "tpu"
    inputs = jax.block_until_ready(make_inputs(c, seed))
    marks.append(("inputs", time.perf_counter()))
    solve = cfg.solver(traffic, n, c.mesh)
    if fault is not None:
        solve = fault(solve, n)

    def bench_solve(*args):
        with jax.named_scope(SOLVE_NAME):
            return solve(*args)

    compiled = jax.jit(bench_solve).lower(*inputs).compile()
    marks.append(("compile", time.perf_counter()))
    if on_tpu and not holds_mosaic_kernel(compiled.as_text()):
        raise RuntimeError("the timed program holds no Mosaic kernel "
                           "(tpu_custom_call): the Pallas path did not "
                           "compile for the chip")
    jax.block_until_ready(compiled(*inputs))    # warm-up, not kept
    marks.append(("warm-up", time.perf_counter()))
    peak = None
    if trace and on_tpu:
        from bench.peaks import lookup, measure_vector_peak
        peak = dict(lookup(devices[0].device_kind),
                    measured=measure_vector_peak())
    # set-up's objects collected and frozen: no collection in the window
    # walks them again
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_process
    log("# setup: " + ", ".join(
        f"{name} {b - a:.3f} s" for (_, a), (name, b)
        in zip([("", t_process)] + marks, marks)))

    trace_dir = TRACE_DIR / workload
    counter = CompileCounter()
    with counter:
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench_window"):
            out, solves, t_start, t_end = time_window(
                lambda: compiled(*inputs), jax.block_until_ready, seconds)
        if trace:
            jax.profiler.stop_trace()
    window_s = t_end - t_start
    log(f"# window: {solves} solves of {n} trajectories in {window_s!r} s; "
        f"compiles inside the window: {counter.count}")

    device = device_info(devices)
    checks = check(c, out, inputs, seed)
    correct = counter.count == 0 and all(v <= lim for _, v, lim in checks)
    checks.append(("compiles_in_window", float(counter.count), 0.0))

    result = {"correct": bool(correct), "attempted": solves,
              "failed": 0 if correct else solves}
    if trace:
        metrics, extra = read_trace(spec, workload, trace_dir, cfg, traffic,
                                    out, n, chips, solves, peak)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(extra.pop("device"))
        result.update(metrics=metrics, device=device, **extra)
    else:
        metrics = {"traj_per_s": {"value": n * solves / window_s,
                                  "unit": "traj/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        wanted = {m["name"] for m in metrics_for(spec, "end_to_end",
                                                 workload)}
        result.update(metrics={k: v for k, v in metrics.items()
                               if k in wanted}, device=device)
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    for name, v, lim in checks:
        log(f"check {name} = {v!r} (limit {lim!r})"
            f"{'' if v <= lim else '  FAILED'}")
    return result


def read_trace(spec, workload, trace_dir, cfg, traffic, out, n, chips,
               solves, peak):
    """Reduce the window's trace and run every per-layer reader of the
    cell.  Returns (metrics, extra) with extra's `device` fields and the
    `breakdown`."""
    import glob

    import jax

    from bench import trace as tr

    path = sorted(glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    trace = tr.load(path)
    span = tr.find_span(trace, "bench_window")
    stats = tr.reduce(trace, span, f"jit_{SOLVE_NAME}")
    tr.check_solves(stats, solves)
    work = cfg.work(traffic)
    attempts = (np.asarray(jax.device_get(out["naccept"]), np.int64)
                + np.asarray(jax.device_get(out["nreject"]), np.int64))
    reading = SimpleNamespace(
        devices=stats, window_ns=span[1] - span[0], n=n, chips=chips,
        solves=solves, attempts=lane_blocks(attempts, chips),
        lanes=[len(b) for b in np.array_split(np.arange(n), chips)],
        nf=int(jax.device_get(out["nf"])), work=work, peak=peak)
    metrics = {}
    for m in metrics_for(spec, "per_layer", workload):
        value = load_metric(m["name"]).read(reading)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    busy_s = float(np.mean([d.busy_ns for d in stats])) * 1e-9
    worst = max(stats, key=lambda d: span[1] - span[0] - d.busy_ns)
    ops = sorted(worst.op_ns.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(worst.gaps, key=lambda g: g[0] - g[1])[:10]
    breakdown = {
        "device_ops": [[name, ns * 1e-9] for name, ns in ops],
        "idle_gaps": [[tr.name_gap(trace, g), (g[1] - g[0]) * 1e-9]
                      for g in gaps]}
    if peak is not None:
        kernel_s = max(d.mosaic_ns / d.solves for d in stats
                       if d.solves) * 1e-9
        bytes_chip = n // chips * work["bytes_per_traj"]
        hbm_s = bytes_chip / peak["hbm_bytes_per_s"]
        log(f"# vector peak measured {peak['measured']['best']!r} op/s, "
            f"table {peak['vector_ops_per_s']!r}; by shape "
            f"{peak['measured']['rates']}")
        log(f"# HBM bytes per solve per chip {bytes_chip}: {hbm_s!r} s at "
            f"{peak['hbm_bytes_per_s']!r} B/s against {kernel_s!r} s of "
            f"kernel; bound by {'HBM' if hbm_s > kernel_s else 'vector ops'}")
    return metrics, {"device": {"busy_s": busy_s,
                                "window_s": (span[1] - span[0]) * 1e-9},
                     "breakdown": breakdown}
