"""The chip benchmark: one run of one cell.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Cells, metrics and bounds are in `BENCHMARK.json` at the checkout root.
The last line of standard output is one JSON object: ``correct``,
``attempted`` (solves in the window: every solve dispatched there,
counted once it ended), ``failed`` (solves whose output the checks
refused), ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` the
``breakdown``, and last ``checks``: each compared number with its limit.
The same numbers end standard error.

No CPU fallback: on a device that is not a TPU, or with fewer chips than
the cell asks for, the run exits non-zero and prints no result.
``--rehearse N`` runs the cell at N trajectories with the Pallas kernels
interpreted, on whatever device there is, prints the result to standard
error and exits 3: it is for the CPU tests only.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

EXIT_NO_CHIP = 2
EXIT_REHEARSAL = 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, default=None, metavar="N",
                    help="CPU rehearsal at N trajectories; never a result")
    args = ap.parse_args(argv)

    from bench import harness
    cell, _ = harness.find_cell(harness.load_spec(), args.workload)
    harness.use_compile_cache()
    import jax
    devices = jax.devices()
    if args.rehearse is None:
        if devices[0].platform != "tpu":
            harness.log(f"bench: device platform is {devices[0].platform!r}"
                        ", not 'tpu'; no CPU fallback")
            return EXIT_NO_CHIP
        if len(devices) < cell["chips"]:
            harness.log(f"bench: {args.workload} needs {cell['chips']} chips"
                        f", found {len(devices)}")
            return EXIT_NO_CHIP
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), t_process=T_PROCESS,
                              rehearse_n=args.rehearse)
    line = json.dumps(result)
    if args.rehearse is not None:
        harness.log("rehearsal " + line)
        return EXIT_REHEARSAL
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
