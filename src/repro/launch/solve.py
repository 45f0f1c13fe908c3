"""Ensemble-solve launcher: `python -m repro.launch.solve --problem lorenz
--n 100000 --ensemble kernel` — the production entry for the paper's workload.

With --mesh local the trajectory axis is shard_mapped over every available
device (the MPI composition of §6.3); straggler mitigation via the
over-decomposed WorkQueue is exercised with --work-queue.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.de_problems import (crn_problem, gbm_problem,
                                       lorenz_ensemble)
from repro.core import EnsembleProblem
from repro.core.api import ensemble_moments, solve_ensemble
from repro.core.sde import solve_sde_ensemble
from repro.dist.fault import WorkQueue
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_local_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--problem", default="lorenz",
                    choices=["lorenz", "gbm", "crn"])
    ap.add_argument("--n", type=int, default=65536)
    ap.add_argument("--ensemble", default="kernel",
                    choices=["kernel", "vmap", "array"])
    ap.add_argument("--backend", default="xla", choices=["xla", "pallas"])
    ap.add_argument("--adaptive", action="store_true")
    ap.add_argument("--dt", type=float, default=1e-3)
    ap.add_argument("--lane-tile", type=int, default=1024)
    ap.add_argument("--mesh", default="none", choices=["none", "local"])
    ap.add_argument("--work-queue", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    t0 = time.perf_counter()
    if args.problem == "lorenz":
        ep = lorenz_ensemble(args.n, dtype=jnp.float32)
        mesh = make_local_mesh() if args.mesh == "local" else None
        if args.work_queue:
            # straggler-tolerant tiling: stateless tiles, safe re-execution
            q = WorkQueue(args.n, tile=args.lane_tile * 8)
            outs = np.zeros((args.n, 3), np.float32)
            while not q.finished:
                claim = q.claim()
                if claim is None:
                    break
                idx, (start, size), tok = claim
                u0s, ps = ep.materialize()
                sub = EnsembleProblem(ep.prob, size,
                                      u0s=u0s[start:start + size],
                                      ps=ps[start:start + size])
                res = solve_ensemble(sub, mesh=None, ensemble=args.ensemble,
                                     adaptive=args.adaptive, dt0=args.dt,
                                     t0=0.0, tf=1.0, save_every=1000,
                                     lane_tile=args.lane_tile)
                outs[start:start + size] = np.asarray(res.u_final)
                q.complete(idx, tok)
            u_final = outs
        else:
            res = solve_ensemble(ep, mesh=mesh, ensemble=args.ensemble,
                                 backend=args.backend,
                                 adaptive=args.adaptive, dt0=args.dt, t0=0.0,
                                 tf=1.0, save_every=1000,
                                 lane_tile=args.lane_tile,
                                 **({"saveat": jnp.asarray([1.0])}
                                    if args.adaptive else {}))
            u_final = np.asarray(res.u_final)
        print(f"{args.n:,} trajectories in {time.perf_counter()-t0:.2f}s "
              f"({args.n/(time.perf_counter()-t0):,.0f} traj/s)  "
              f"mean |u_f| = {np.abs(u_final).mean():.4f}")
    else:
        prob = gbm_problem() if args.problem == "gbm" else crn_problem(
            tspan=(0.0, 10.0))
        ep = EnsembleProblem(prob, args.n)
        res = solve_sde_ensemble(ep, jax.random.PRNGKey(0), args.dt,
                                 int(round(prob.tspan[1] / args.dt)),
                                 ensemble="kernel",
                                 save_every=int(round(prob.tspan[1]
                                                      / args.dt)))
        mean, var = ensemble_moments(res.u_final)
        print(f"{args.n:,} SDE paths in {time.perf_counter()-t0:.2f}s  "
              f"E[X_T] = {np.asarray(mean)}  Var = {np.asarray(var)}")


if __name__ == "__main__":
    main()
