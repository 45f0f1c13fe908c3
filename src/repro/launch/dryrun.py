import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^^ MUST precede every other import (jax locks the device count on first
# init). This process-level override exists ONLY for the dry-run: smoke tests
# and benchmarks see the real single device.

"""Multi-pod dry-run (deliverable e): .lower().compile() every
(architecture x input-shape x mesh) cell against the production mesh and
record memory/cost/collective analysis for §Dry-run / §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k \
      --mesh single --out results/
  python -m repro.launch.dryrun --all --mesh both --out results/
  python -m repro.launch.dryrun --ode     # the paper's 2^30-trajectory cell
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.archs import ARCHS, LONG_CONTEXT_SKIP, get_arch
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (abstract_params, input_specs,
                                train_batch_specs)
from repro.models.config import SHAPES
from repro.models.model import build_model
from repro.optim.adamw import AdamW, cosine_schedule
from repro.train.serve import make_serve_plan
from repro.train.trainer import make_train_step, pick_accum

# --------------------------------------------------------------------------
# HLO collective accounting
# --------------------------------------------------------------------------

_COLL_RE = re.compile(
    r"^\s*(?:%?[\w.\-]+\s*=\s*)?"
    r"((?:[a-z0-9]+\[[^\]]*\](?:,\s*)?)+|\(.*?\))\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)",
    re.M)
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|s32|u64|u32|s8|u8|pred)\[([0-9,]*)\]")

_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "s32": 4,
          "u64": 8, "u32": 4, "s8": 1, "u8": 1, "pred": 1}


def collective_bytes(hlo_text: str):
    """Sum result-shape bytes of every collective op in post-SPMD HLO.
    Returns (total_bytes_per_device, counts_by_op)."""
    total = 0
    counts = {}
    for m in _COLL_RE.finditer(hlo_text):
        shapes_str, op = m.group(1), m.group(2)
        sz = 0
        for sm in _SHAPE_RE.finditer(shapes_str):
            dt, dims = sm.group(1), sm.group(2)
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            sz += n * _BYTES[dt]
        total += sz
        counts[op] = counts.get(op, 0) + 1
    return total, counts


def analyze(lowered, compiled):
    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    cbytes, ccounts = collective_bytes(hlo)
    out = {
        "flops": float(cost.get("flops", -1.0)),
        "hlo_bytes": float(cost.get("bytes accessed", -1.0)),
        "collective_bytes": cbytes,
        "collective_counts": ccounts,
    }
    if mem is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            try:
                out[k] = int(getattr(mem, k))
            except Exception:
                pass
    return out


# --------------------------------------------------------------------------
# per-cell lowering
# --------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool,
             fsdp: bool = True, extra_tag: str = "",
             shard_mode: str = None, remat_mode="full") -> dict:
    """Lower + compile one cell."""
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "multi" if multi_pod else "single",
           "tag": extra_tag, "ok": False}
    unroll = 1
    remat = "dots" if remat_mode == "dots" else True
    t0 = time.time()
    try:
        if shape.kind == "train":
            model = build_model(cfg, dtype=jnp.bfloat16, remat=remat,
                                unroll=unroll)
            nd = mesh.devices.size // mesh.shape["model"]
            per_dev = shape.global_batch // nd
            accum = pick_accum(cfg, per_dev, shape.seq_len)
            rec["accum"] = accum
            opt = AdamW(lr=cosine_schedule(3e-4, 100, 10_000))
            batch = train_batch_specs(cfg, shape)
            plan = make_train_step(model, opt, mesh=mesh, accum=accum,
                                   fsdp=fsdp, abstract_batch=batch,
                                   shard_mode=shard_mode)
            lowered = plan.step_fn.lower(plan.abstract_params,
                                         plan.abstract_opt, batch)
        elif shape.kind == "prefill":
            model = build_model(cfg, dtype=jnp.bfloat16, remat=True,
                                unroll=unroll)
            batch = train_batch_specs(cfg, shape)
            plan = make_serve_plan(model, mesh, shape.global_batch,
                                   shape.seq_len, fsdp=fsdp,
                                   abstract_batch=batch)
            lowered = plan.prefill_fn.lower(plan.abstract_params, batch)
        else:  # decode
            model = build_model(cfg, dtype=jnp.bfloat16, unroll=unroll)
            plan = make_serve_plan(model, mesh, shape.global_batch,
                                   shape.seq_len, fsdp=fsdp)
            toks = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
            lowered = plan.decode_fn.lower(plan.abstract_params,
                                           plan.abstract_cache, toks)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)
        rec.update(analyze(lowered, compiled))
        rec["n_devices"] = int(mesh.devices.size)
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001 — record and continue the batch
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 1)
    return rec


def run_ode_cell(multi_pod: bool, n_traj: int = 2 ** 30) -> dict:
    """The paper's §6.3 scaling demo as a dry-run: 2^30 Lorenz trajectories
    sharded over the production mesh (ensemble axis = pod x data)."""
    from repro.core.api import solve_ensemble
    from repro.core.problem import EnsembleProblem
    from repro.configs.de_problems import lorenz_problem
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": "lorenz-ensemble", "shape": f"traj_{n_traj}",
           "mesh": "multi" if multi_pod else "single", "ok": False}
    t0 = time.time()
    try:
        prob = lorenz_problem(jnp.float32)
        ep = EnsembleProblem(prob, n_traj)

        def solve(u0s, ps):
            ep2 = EnsembleProblem(prob, n_traj, u0s=u0s, ps=ps)
            res = solve_ensemble(ep2, mesh=mesh, ensemble="kernel",
                                 backend="xla", adaptive=False, dt0=1e-3,
                                 t0=0.0, tf=1.0, save_every=1000,
                                 lane_tile=4096)
            return res.u_final

        u0s = jax.ShapeDtypeStruct((n_traj, 3), jnp.float32)
        ps = jax.ShapeDtypeStruct((n_traj, 3), jnp.float32)
        lowered = jax.jit(solve).lower(u0s, ps)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)
        rec.update(analyze(lowered, compiled))
        rec["n_devices"] = int(mesh.devices.size)
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 1)
    return rec


def cells(include_skipped=False):
    for arch in ARCHS:
        for shape in SHAPES:
            if shape == "long_500k" and arch in LONG_CONTEXT_SKIP \
                    and not include_skipped:
                continue
            yield arch, shape


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--ode", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--out", default="results")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    todo = []
    if args.ode:
        todo = [("__ode__", None)]
    elif args.all:
        todo = list(cells())
    else:
        todo = [(args.arch, args.shape)]

    for arch, shape in todo:
        for mp in meshes:
            if arch == "__ode__":
                rec = run_ode_cell(mp)
                name = f"ode_{'multi' if mp else 'single'}"
            else:
                rec = run_cell(arch, shape, mp, fsdp=not args.no_fsdp)
                name = f"{arch}_{shape}_{'multi' if mp else 'single'}"
            path = os.path.join(args.out, name + ".json")
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            status = "OK" if rec["ok"] else f"FAIL: {rec.get('error')}"
            print(f"[dryrun] {name}: {status} ({rec['total_s']}s)",
                  flush=True)


if __name__ == "__main__":
    main()
