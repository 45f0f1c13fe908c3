"""Readings that set the limits of a cell's checks: the program's and the control's.

    python bench/control.py --workload <name> --seeds 1,2,...,12 \
        --control-seeds 101,102,103

For every seed, one solve of the timed program at the cell's size and the
cell's compared numbers (the lower readings: the largest over the seeds is
what a sound run reads); then the same numbers for the configuration's
control, its plain method in the nearest precision below the stated one,
put in the program's place (the upper readings: the smallest is what a run
one precision down reads).  A limit lies between the two.  One JSON line
per seed and side, then a summary line.  Not part of a benchmark run.

Like `bench/run.py` it refuses a device that is not a TPU unless given
``--rehearse N``, which runs at N trajectories on any device.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def readings(workload: str, seeds, side: str, n: int = None):
    """Yield (seed, {name: (value, limit)}) for the program ('program') or
    the control ('control'), one solve per seed, one compile in all."""
    import jax

    from bench import harness

    c = harness.setup_cell(workload, n)
    fn = (c.cfg.solver(c.traffic, c.n, c.mesh) if side == "program"
          else c.cfg.control(c.traffic, c.n))
    compiled = None
    for seed in seeds:
        inputs = harness.make_inputs(c, seed)
        if compiled is None:
            compiled = jax.jit(fn).lower(*inputs).compile()
        out = jax.block_until_ready(compiled(*inputs))
        yield seed, {name: (v, lim)
                     for name, v, lim in harness.check(c, out, inputs, seed)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rehearse", type=int, default=None, metavar="N")
    args = ap.parse_args(argv)

    from bench import harness
    harness.use_compile_cache()
    import jax
    if args.rehearse is None and jax.devices()[0].platform != "tpu":
        harness.log("control: not a TPU; pass --rehearse N to rehearse")
        return 2
    summary = {}
    for side, seeds in (("program", args.seeds),
                        ("control", args.control_seeds)):
        seeds = [int(s) for s in seeds.split(",") if s]
        t = time.perf_counter()
        for seed, nums in readings(args.workload, seeds, side, args.rehearse):
            print(json.dumps({"side": side, "seed": seed, "numbers": nums}),
                  flush=True)
            for name, (v, lim) in nums.items():
                pick = max if side == "program" else min
                key = (side, name)
                summary[key] = v if key not in summary else pick(
                    summary[key], v)
        harness.log(f"# {side}: {len(seeds)} seeds in "
                    f"{time.perf_counter() - t!r} s")
    print(json.dumps({"summary": {
        f"{side}_{'max' if side == 'program' else 'min'}.{name}": v
        for (side, name), v in summary.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
