"""Benchmark driver: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only fig4,...]``
Prints ``name,us_per_call,derived`` CSV rows per module.
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback

# allow both `python -m benchmarks.run` and `python benchmarks/run.py`
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# a module may carry several pipe-separated tags ("fig4|crossover"):
# --only matches any of them, so `--only crossover` selects the pair of
# benches that write results/BENCH_crossover.json
MODULES = [
    ("fig4|crossover", "benchmarks.bench_fig4_crossover"),
    ("table1", "benchmarks.bench_table1_speedups"),
    ("fig56|crossover", "benchmarks.bench_fig56_vs_vmap"),
    ("fig7", "benchmarks.bench_fig7_backends"),
    ("fig9", "benchmarks.bench_fig9_gbm"),
    ("adaptive_sde", "benchmarks.bench_adaptive_sde"),
    ("stiff", "benchmarks.bench_stiff"),
    ("gradients", "benchmarks.bench_gradients"),
    ("fig11", "benchmarks.bench_fig11_crn"),
    ("texture", "benchmarks.bench_texture_interp"),
    ("serving", "benchmarks.bench_serving"),
    ("elastic", "benchmarks.bench_elastic"),
]


def check_bench_imports(modname: str) -> None:
    """Bitrot guard for `--dry`: bench modules import their shared helpers
    lazily inside main() (so a dry import stays cheap), which means a plain
    import check never executes `from .common import bench, row` — rename a
    helper in common.py and every benchmark breaks only at timing time.
    Statically walk the module's AST and verify every name imported from
    within the benchmarks package actually exists."""
    import ast
    import importlib
    import inspect

    mod = importlib.import_module(modname)
    tree = ast.parse(inspect.getsource(mod))
    for node in ast.walk(tree):
        if not isinstance(node, ast.ImportFrom):
            continue
        if node.level:                       # from .common import ...
            target = "benchmarks" + ("." + node.module if node.module else "")
        elif node.module and node.module.startswith("benchmarks"):
            target = node.module
        else:
            continue
        tmod = importlib.import_module(target)
        for alias in node.names:
            if alias.name != "*" and not hasattr(tmod, alias.name):
                raise AssertionError(
                    f"{modname}: `from {target} import {alias.name}` names "
                    "a symbol that no longer exists (signature drift)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--dry", action="store_true",
                    help="import every benchmark module and check its entry "
                         "point without timing anything (CI smoke)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    import importlib
    failed = []
    for tag, modname in MODULES:
        if only and not (only & set(tag.split("|"))):
            continue
        if args.dry:
            try:
                mod = importlib.import_module(modname)
                assert callable(getattr(mod, "main")), f"{modname}.main"
                check_bench_imports(modname)
                print(f"# {modname}: ok")
            except Exception as e:  # noqa: BLE001 — report all, then fail
                failed.append(modname)
                print(f"# {modname} FAILED: {type(e).__name__}: {e}")
            continue
        print(f"\n# ==== {modname} ====")
        try:
            importlib.import_module(modname).main()
        except Exception as e:  # noqa: BLE001 — run the rest, then fail
            failed.append(modname)
            print(f"# {modname} FAILED: {type(e).__name__}: {e}")
            traceback.print_exc()
    if failed:
        print(f"# {len(failed)} module(s) failed: {', '.join(failed)}")
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
