"""Ensemble execution strategies (paper §5) on a single device — all families.

`solve_ensemble_local` is the single front door: ANY registered method
(`repro.core.methods` — explicit RK, Rosenbrock-stiff, SDE steppers) through
ANY strategy and backend.

Strategies (``ensemble=``):

  "array"       EnsembleGPUArray semantics (§5.1): the whole ensemble is ONE
                state matrix stepped in lock-step with a single global dt chosen
                by an ensemble-wide error norm. One slow trajectory stalls all N.
  "array_eager" As above but stepped from Python with un-jitted array ops —
                faithfully reproduces the per-op dispatch overhead of the
                array-abstraction frameworks the paper benchmarks (PyTorch
                eager; each jnp op is a separate dispatch, i.e. "kernel launch").
  "vmap"        The JAX/Diffrax baseline the paper compares against:
                ``vmap(solve_one)`` — per-trajectory dt, but vmap-of-while lowers
                to masked lock-step iteration over the WHOLE batch: every
                trajectory pays max-steps-of-any.
  "kernel"      The paper's contribution (§5.2) adapted to TPU: trajectories are
                vector lanes; the full integration loop is fused into one
                computation per lane-tile; tiles retire independently.
                backend="xla"    — fused lax.while_loop per tile (lax.map over
                                   tiles); measured-benchmark path on CPU.
                backend="pallas" — the generic ensemble Pallas kernel
                                   (kernels/ensemble_kernel) with VMEM-resident
                                   state; the deployment path. lane_tile=None
                                   derives the tile from the §5.2 VMEM formula.

Method families (``alg=`` resolves via the registry; full matrix in
docs/architecture.md):

  erk         — all strategies/backends; adaptive or fixed dt; events.
  rosenbrock  — "vmap", "array" (one lanes tile) and "kernel" (xla/pallas);
                the W = I - γh·J solves (paper §5.1.3) run batched per lane,
                inlined inside the Pallas kernel; events on every path.
  sde         — "vmap", "array" and "kernel" (xla/pallas); fixed-dt
                counter-RNG steppers (§5.2.2) or, with adaptive=True,
                per-trajectory error control driven by a virtual Brownian
                tree (rejection-safe noise): an embedded pair where one is
                registered (error_est="embedded", the default) or step
                doubling (error_est="doubling"). Pass `seed=` (or `key=`) — the
                SAME (seed; step, row, GLOBAL lane) Threefry stream is
                replayed on every strategy/backend, so paths agree bitwise
                across dispatch targets (and across mesh shards via
                `lane_offset`); or inject `noise_table=` (n_steps, m, N).
                Events run with per-lane termination on every path.

Distribution over a mesh (the paper's MPI composition, §6.3) lives in
`repro.core.api.solve_ensemble` via shard_map over the trajectory axis.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .controller import PIController
from .interp import (data_flatten, data_unflatten, data_words,
                     kernel_lookups)
from .methods import MethodSpec, get_method
from .problem import (EnsembleProblem, ODEProblem, SDEProblem,
                      bind_problem_data)
from .solvers import (AdaptiveOptions, Event, SolveResult, interp_step,
                      rk_step, solve_adaptive, solve_fixed, solve_one)
from .tableaus import Tableau

Array = Any

# default lane tile for the XLA lanes path (the Pallas path derives its tile
# from the VMEM formula instead — see kernels/ensemble_kernel.auto_lane_tile)
XLA_LANE_TILE = 256


class EnsembleResult(NamedTuple):
    # NamedTuple (a pytree): results flow through jit/shard_map boundaries
    ts: Array        # (S,)
    us: Array        # (N, S, n)
    u_final: Array   # (N, n)
    t_final: Array   # (N,)
    naccept: Array   # per-trajectory or broadcast scalar
    nreject: Array
    nf: Array        # total RHS evaluations (work proxy; paper's overhead story)
    status: Array
    njac: Array = 0  # total Jacobian evaluations (stiff family; 0 elsewhere)
    nfact: Array = 0  # total W = I − γh·J factorizations (stiff family)
    # (N,) int32: loop iterations the trajectory's lane tile ran, at least
    # its naccept + nreject; lockstep occupancy is sum(naccept + nreject) /
    # sum(steps_run), added on the host in int64.  None on paths without
    # lane tiles (vmap, the erk array strategy, solve_kernel_fixed)
    steps_run: Optional[Array] = None


def _pad_to(x, n_target, axis=0):
    pad = n_target - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, mode="edge")


def _tile_lanes(u0s, ps, lane_tile):
    """(N, k)-major arrays -> (T, B, k) tiles for the XLA lanes path.

    The vector width matches `kernels.ensemble_kernel.padded_lane_width`
    exactly: XLA codegen is width-sensitive at the ulp level (FMA/SIMD
    contraction), so the lanes oracle and the Pallas kernel must run the
    SAME width to stay bitwise-comparable.  (`array` strategy passes
    lane_tile == N and keeps the whole-ensemble width.)"""
    from repro.kernels.ensemble_kernel import padded_lane_width
    N = u0s.shape[0]
    B = padded_lane_width(N, lane_tile)
    T = -(-N // B)
    u0p = _pad_to(u0s, T * B).reshape(T, B, u0s.shape[1])
    psp = _pad_to(ps, T * B).reshape(T, B, ps.shape[1])
    return u0p, psp, T, B


def _untile(res, N, n):
    """Invert _tile_lanes on a lanes-mode SolveResult mapped over tiles."""
    us = jnp.moveaxis(res.us, -1, 1).reshape(-1, res.us.shape[1], n)[:N]
    u_final = jnp.moveaxis(res.u_final, -1, 1).reshape(-1, n)[:N]

    def total(v):
        # per-lane (T, B) work counters -> padded-lane-free total; scalar
        # defaults (non-stiff families leave njac/nfact at 0) pass through
        if jnp.ndim(v) == 0:
            return jnp.asarray(v)
        return jnp.sum(v.reshape(-1)[:N])

    return EnsembleResult(
        ts=res.ts[0], us=us, u_final=u_final,
        t_final=res.t_final.reshape(-1)[:N],
        naccept=res.naccept.reshape(-1)[:N],
        nreject=res.nreject.reshape(-1)[:N],
        nf=total(res.nf),
        status=jnp.max(res.status),
        njac=total(res.njac), nfact=total(res.nfact),
        steps_run=jnp.broadcast_to(res.iters[:, None],
                                   res.naccept.shape).reshape(-1)[:N])


# ----------------------------------------------------------------------------
# strategy: vmap (the JAX-baseline the paper beats 20-100x)
# ----------------------------------------------------------------------------

def solve_vmap(prob: ODEProblem, u0s, ps, tab, t0, tf, dt0, saveat,
               rtol, atol, adaptive, max_iters, event=None,
               bounded_steps=None, checkpoint_every=None) -> EnsembleResult:
    def one(u0, p):
        return solve_one(prob.f, tab, u0, p, t0, tf, dt0, saveat=saveat,
                         rtol=rtol, atol=atol, adaptive=adaptive,
                         max_iters=max_iters, event=event,
                         bounded_steps=bounded_steps,
                         checkpoint_every=checkpoint_every)

    res = jax.vmap(one)(u0s, ps)
    if event is not None:
        res, _ = res
    return EnsembleResult(ts=saveat, us=res.us, u_final=res.u_final,
                          t_final=res.t_final, naccept=res.naccept,
                          nreject=res.nreject, nf=jnp.sum(res.nf),
                          status=jnp.max(res.status))


# ----------------------------------------------------------------------------
# strategy: array (EnsembleGPUArray semantics: lock-step global dt)
# ----------------------------------------------------------------------------

def solve_array(prob: ODEProblem, u0s, ps, tab, t0, tf, dt0, saveat,
                rtol, atol, adaptive, max_iters, event=None,
                bounded_steps=None, checkpoint_every=None) -> EnsembleResult:
    # stack to (n, N): component-style f broadcasts over the trailing lane axis,
    # scalar-control mode gives ONE dt + ensemble-wide norm == §5.1 semantics.
    U0 = u0s.T
    P = ps.T
    opts = AdaptiveOptions(rtol=rtol, atol=atol, max_iters=max_iters,
                           adaptive=adaptive, bounded_steps=bounded_steps,
                           checkpoint_every=checkpoint_every)
    res = solve_adaptive(prob.f, tab, U0, P, t0, tf, dt0, saveat=saveat,
                         opts=opts, event=event, lanes=False)
    if event is not None:
        res, _ = res
    N = u0s.shape[0]
    return EnsembleResult(
        ts=saveat, us=jnp.moveaxis(res.us, -1, 0),       # (S,n,N)->(N,S,n)
        u_final=res.u_final.T, t_final=jnp.broadcast_to(res.t_final, (N,)),
        naccept=res.naccept, nreject=res.nreject,
        nf=res.nf * N,  # every global step evaluates f for all N columns
        status=res.status)


def solve_array_eager(prob: ODEProblem, u0s, ps, tab, t0, tf, dt0, saveat,
                      rtol, atol, adaptive, max_steps=100_000) -> EnsembleResult:
    """Python-driven lock-step loop with per-op dispatch (no jit around the
    step). This is the honest analogue of the eager array-abstraction overhead
    the paper attributes 10-100x to: every jnp op below is a separate dispatch
    ("kernel launch"), every step a host-device synchronization."""
    ctrl = PIController.for_order(tab.embedded_order)
    U = u0s.T
    P = ps.T
    t = float(t0)
    dt = float(dt0)
    enorm_prev = 1.0
    saveat_np = np.asarray(saveat)
    S = len(saveat_np)
    us = np.zeros((S,) + U.shape, dtype=np.asarray(U).dtype)
    sidx = 0
    naccept = nreject = 0
    U_prev = U
    while t < float(tf) - 1e-12 and (naccept + nreject) < max_steps:
        dt_step = min(dt, float(tf) - t)
        k1 = prob.f(U, P, t)
        U_new, err, ks = rk_step(prob.f, tab, U, P, t, dt_step, k1)
        if adaptive:
            scale = atol + np.maximum(np.abs(U), np.abs(U_new)) * rtol
            enorm = float(jnp.sqrt(jnp.mean((err / scale) ** 2)))
            accept = enorm <= 1.0
            e = max(enorm, 1e-10)
            if accept:
                fac = float(np.clip(ctrl.safety * e ** (-ctrl.beta1)
                                    * max(enorm_prev, 1e-10) ** ctrl.beta2,
                                    ctrl.qmin, ctrl.qmax))
                enorm_prev = e
            else:
                fac = float(np.clip(ctrl.safety * e ** (-ctrl.beta1),
                                    ctrl.qmin, 1.0))
            dt = dt_step * fac
        else:
            accept = True
        if accept:
            t_new = t + dt_step
            while sidx < S and saveat_np[sidx] <= t_new + 1e-12:
                theta = np.clip((saveat_np[sidx] - t) / dt_step, 0.0, 1.0)
                us[sidx] = np.asarray(
                    interp_step(prob.f, tab, U, U_new, ks, P, t, dt_step,
                                jnp.asarray(theta, U.dtype)))
                sidx += 1
            U = U_new
            t = t_new
            naccept += 1
        else:
            nreject += 1
    N = u0s.shape[0]
    return EnsembleResult(
        ts=saveat, us=jnp.moveaxis(jnp.asarray(us), -1, 0),
        u_final=U.T, t_final=jnp.full((N,), t),
        naccept=jnp.asarray(naccept), nreject=jnp.asarray(nreject),
        nf=jnp.asarray((naccept + nreject) * tab.stages * N),
        status=jnp.asarray(0 if t >= float(tf) - 1e-9 else 1))


# ----------------------------------------------------------------------------
# strategy: kernel (paper §5.2) — fused whole-integration per lane-tile
# ----------------------------------------------------------------------------

def solve_kernel_xla(prob: ODEProblem, u0s, ps, tab, t0, tf, dt0, saveat,
                     rtol, atol, adaptive, max_iters, lane_tile=XLA_LANE_TILE,
                     event=None, bounded_steps=None,
                     checkpoint_every=None) -> EnsembleResult:
    """Fused-integration lanes path expressed in pure XLA.

    Trajectories are packed into (n, B) tiles; each tile runs ONE while_loop to
    completion (per-lane dt/accept masks), and tiles are processed by lax.map —
    the exact control structure of the Pallas kernel, so this backend doubles
    as its oracle and as the measured-CPU-benchmark path.
    """
    N, n = u0s.shape
    u0p, psp, T, B = _tile_lanes(u0s, ps, lane_tile)
    opts = AdaptiveOptions(rtol=rtol, atol=atol, max_iters=max_iters,
                           adaptive=adaptive, bounded_steps=bounded_steps,
                           checkpoint_every=checkpoint_every)

    def tile(args):
        u0t, pt = args  # (B,n), (B,m)
        res = solve_adaptive(prob.f, tab, u0t.T, pt.T, t0, tf, dt0,
                             saveat=saveat, opts=opts, event=event, lanes=True)
        if event is not None:
            res, _ = res
        return res

    return _untile(jax.lax.map(tile, (u0p, psp)), N, n)


def solve_kernel_fixed(prob: ODEProblem, u0s, ps, tab, t0, dt, n_steps,
                       save_every, lane_tile=1024, remat=False,
                       checkpoint_every=None) -> EnsembleResult:
    """Fixed-dt fused path: scan-of-steps over (n, N) lanes — single fused
    computation, O(1) state traffic per step (the paper's fixed-dt kernel)."""
    N, n = u0s.shape
    res = solve_fixed(prob.f, tab, u0s.T, ps.T, t0, dt, n_steps, save_every,
                      remat=remat, checkpoint_every=checkpoint_every)
    ts = res.ts
    return EnsembleResult(
        ts=ts, us=jnp.moveaxis(res.us, -1, 0),
        u_final=res.u_final.T,
        t_final=jnp.broadcast_to(res.t_final, (N,)),
        naccept=jnp.broadcast_to(res.naccept, (N,)),
        nreject=jnp.zeros((N,), jnp.int32),
        nf=res.nf * N, status=res.status)


# ----------------------------------------------------------------------------
# sensitivity plumbing shared by the family dispatchers
# ----------------------------------------------------------------------------

def _resolve_adjoint(sensitivity, adaptive, adjoint_steps, n_steps):
    """(bounded_steps, remat) for the engines under sensitivity='adjoint'.

    Adaptive stepping has no static iteration count, so reverse mode needs an
    explicit ``adjoint_steps`` bound (probe the forward solve:
    ``naccept + nreject``; a bound that turns out too small reports
    ``status == 1`` — never a silently wrong gradient).  Fixed-dt stepping
    derives the bound from ``n_steps`` (one attempt per step) and asks the
    scan-shaped paths for segment remat instead.
    """
    if sensitivity != "adjoint":
        return None, False
    if adjoint_steps is not None:
        return int(adjoint_steps), True
    if adaptive:
        raise ValueError(
            "sensitivity='adjoint' with adaptive stepping needs an explicit "
            "adjoint_steps bound on the attempt count (run the forward solve "
            "once and use naccept + nreject plus margin; a too-small bound "
            "surfaces as status == 1, never as a wrong gradient)")
    # fixed-accept stepping: exactly one attempt per step
    return int(n_steps) + 1, True


# ----------------------------------------------------------------------------
# family dispatch: erk
# ----------------------------------------------------------------------------

def _solve_erk(spec: MethodSpec, prob, u0s, ps, *, ensemble, backend, t0, tf,
               dt0, saveat, rtol, atol, adaptive, n_steps, save_every,
               lane_tile, max_iters, event, sensitivity=None,
               adjoint_steps=None, checkpoint_every=None, raw_prob=None):
    # `prob` arrives with any dataset CLOSED OVER its callbacks
    # (bind_problem_data) — every XLA path below consumes it unchanged.  The
    # Pallas branch instead needs the RAW 4-arg callbacks plus the dataset
    # leaves as real kernel/custom_vjp arguments, hence `raw_prob`.
    data = getattr(raw_prob, "data", None)
    dleaves, dtreedef = data_flatten(data)
    tab = spec.tableau
    if adaptive is None:
        adaptive = True   # family default: embedded-error stepping
    if not spec.adaptive:
        adaptive = False  # e.g. rk4: no embedded error estimate
    explicit_saveat = saveat is not None
    if not adaptive and n_steps is None:
        n_steps = int(round((tf - t0) / dt0))
    bounded, remat = _resolve_adjoint(sensitivity, adaptive, adjoint_steps,
                                      n_steps)
    if saveat is None:
        if not adaptive and ensemble == "kernel" and event is None:
            # mirror solve_kernel_fixed's save_every grid so the pallas and
            # xla fixed-step paths produce identical snapshots
            if n_steps % save_every != 0:
                raise ValueError(
                    f"save_every={save_every} must divide n_steps={n_steps}")
            saveat = t0 + dt0 * save_every * jnp.arange(
                1, n_steps // save_every + 1)
        else:
            saveat = [tf]
    saveat = jnp.asarray(saveat, u0s.dtype)

    if ensemble == "vmap":
        return solve_vmap(prob, u0s, ps, tab, t0, tf, dt0, saveat, rtol, atol,
                          adaptive, max_iters, event, bounded_steps=bounded,
                          checkpoint_every=checkpoint_every)
    if ensemble == "array":
        return solve_array(prob, u0s, ps, tab, t0, tf, dt0, saveat, rtol, atol,
                           adaptive, max_iters, event, bounded_steps=bounded,
                           checkpoint_every=checkpoint_every)
    if ensemble == "array_eager":
        if event is not None:
            raise NotImplementedError(
                "events are not supported on the array_eager strategy")
        return solve_array_eager(prob, u0s, ps, tab, t0, tf, dt0, saveat,
                                 rtol, atol, adaptive)
    if ensemble == "kernel":
        if backend == "pallas":
            from repro.kernels.tsit5 import ops as erk_ops
            kprob = raw_prob if data is not None else prob

            def run(u, p, *lv):
                d = data_unflatten(dtreedef, lv) if data is not None else None
                return erk_ops.solve_ensemble_pallas(
                    kprob, u, p, tab, t0, tf, dt0, saveat, rtol, atol,
                    adaptive, lane_tile=lane_tile, max_iters=max_iters,
                    event=event, data=d)

            if sensitivity == "adjoint":
                from repro.kernels.ensemble_kernel import kernel_adjoint

                def replay(u, p, *lv):
                    bp = (bind_problem_data(raw_prob,
                                            data_unflatten(dtreedef, lv))
                          if data is not None else prob)
                    return solve_kernel_xla(
                        bp, u, p, tab, t0, tf, dt0, saveat, rtol, atol,
                        adaptive, max_iters, lane_tile or XLA_LANE_TILE,
                        event, bounded_steps=bounded,
                        checkpoint_every=checkpoint_every)

                return kernel_adjoint(run, replay)(u0s, ps, *dleaves)
            return run(u0s, ps, *dleaves)
        if not adaptive and event is None and not explicit_saveat:
            return solve_kernel_fixed(prob, u0s, ps, tab, t0, dt0, n_steps,
                                      save_every,
                                      lane_tile or XLA_LANE_TILE, remat=remat,
                                      checkpoint_every=checkpoint_every)
        # fixed dt with a user saveat: lanes path with adaptive=False honours
        # the requested grid via dense output
        return solve_kernel_xla(prob, u0s, ps, tab, t0, tf, dt0, saveat,
                                rtol, atol, adaptive, max_iters,
                                lane_tile or XLA_LANE_TILE, event,
                                bounded_steps=bounded,
                                checkpoint_every=checkpoint_every)
    raise ValueError(f"unknown ensemble strategy {ensemble!r}")


# ----------------------------------------------------------------------------
# family dispatch: rosenbrock (stiff, paper §5.1.3 + §7)
# ----------------------------------------------------------------------------

def _solve_rosenbrock(spec: MethodSpec, prob, u0s, ps, *, ensemble, backend,
                      t0, tf, dt0, saveat, rtol, atol, lane_tile, max_iters,
                      linsolve, event, w_reuse, sensitivity=None,
                      adjoint_steps=None, checkpoint_every=None,
                      raw_prob=None):
    from .rosenbrock import solve_rosenbrock

    # dataset plumbing mirrors _solve_erk: bound closures (f AND jac) on the
    # XLA paths, raw callbacks + leaf arguments on the Pallas/adjoint ones
    data = getattr(raw_prob, "data", None)
    dleaves, dtreedef = data_flatten(data)

    # the stiff engine is always adaptive: adjoint mode needs the explicit
    # attempt bound (see _resolve_adjoint)
    bounded, _ = _resolve_adjoint(sensitivity, True, adjoint_steps, None)

    rtab = spec.rtableau
    if not spec.adaptive:
        # btilde == 0: no embedded error estimate.  The stiff engine has no
        # fixed-dt path, and running the PI controller on err ≡ 0 would
        # accept every step at max growth — reject loudly instead.
        raise ValueError(
            f"rosenbrock method {spec.name!r} has no embedded error weights "
            "(btilde == 0); the stiff engine requires an adaptive pair")
    if w_reuse is None:
        w_reuse = spec.w_reuse   # method default; False = eager every step
    jac = getattr(prob, "jac", None)  # analytic-Jacobian hook (jacfwd if None)
    if saveat is None:
        saveat = jnp.asarray([tf], u0s.dtype)
    saveat = jnp.asarray(saveat, u0s.dtype)
    N, n = u0s.shape

    if ensemble == "vmap":
        # bind an axis name so the lazy-W refresh conds stay REAL branches:
        # solve_rosenbrock psum-reduces its predicates over this axis
        # (unbatched bool), instead of vmap lowering them to both-branch
        # selects — w_reuse then saves wall time under vmap too.
        ax = "_repro_vmap_lanes"

        def one(u0, p):
            return solve_rosenbrock(prob.f, rtab, u0, p, t0, tf, dt0,
                                    rtol=rtol, atol=atol, saveat=saveat,
                                    max_iters=max_iters, jac=jac, event=event,
                                    w_reuse=w_reuse, batch_axis=ax,
                                    bounded_steps=bounded,
                                    checkpoint_every=checkpoint_every)

        res = jax.vmap(one, axis_name=ax)(u0s, ps)
        if event is not None:
            res, _ = res
        return EnsembleResult(ts=saveat, us=res.us, u_final=res.u_final,
                              t_final=res.t_final, naccept=res.naccept,
                              nreject=res.nreject, nf=jnp.sum(res.nf),
                              status=jnp.max(res.status),
                              njac=jnp.sum(res.njac),
                              nfact=jnp.sum(res.nfact))

    if ensemble in ("array", "kernel"):
        # "array": whole ensemble as ONE lanes tile. A lock-step scalar-dt
        # Rosenbrock would need an (N·n)-sized Jacobian per global step, so
        # the array strategy keeps the one-state-matrix memory layout but
        # per-lane step control — preserving the cross-strategy trajectory
        # parity contract (identical per-trajectory dt sequences).
        tile_n = N if ensemble == "array" else (lane_tile or XLA_LANE_TILE)

        def lanes_run(u, p, *lv):
            # `*lv` = dataset leaves when replaying a data-driven Pallas
            # solve under kernel_adjoint (grads must reach the tables); a
            # direct XLA solve closes over them via `prob`/`jac` instead
            if lv:
                bp = bind_problem_data(raw_prob, data_unflatten(dtreedef, lv))
                f_loc, jac_loc = bp.f, getattr(bp, "jac", None)
            else:
                f_loc, jac_loc = prob.f, jac
            u0p, psp, T, B = _tile_lanes(u, p, tile_n)

            def tile(args):
                u0t, pt = args
                res = solve_rosenbrock(f_loc, rtab, u0t.T, pt.T, t0, tf, dt0,
                                       rtol=rtol, atol=atol, saveat=saveat,
                                       max_iters=max_iters, lanes=True,
                                       linsolve=linsolve, lane_tile=B,
                                       jac=jac_loc,
                                       event=event, w_reuse=w_reuse,
                                       bounded_steps=bounded,
                                       checkpoint_every=checkpoint_every)
                if event is not None:
                    res, _ = res
                return res

            return _untile(jax.lax.map(tile, (u0p, psp)), N, n)

        if ensemble == "kernel" and backend == "pallas":
            from repro.kernels.ensemble_kernel import (kernel_adjoint,
                                                       rosenbrock_body,
                                                       rosenbrock_work_words,
                                                       run_ensemble_kernel)
            kf = raw_prob.f if data is not None else prob.f
            kjac = (getattr(raw_prob, "jac", None) if data is not None
                    else jac)
            body = rosenbrock_body(kf, rtab, jac=kjac, t0=float(t0),
                                   tf=float(tf), dt0=float(dt0),
                                   rtol=float(rtol), atol=float(atol),
                                   max_iters=max_iters, event=event,
                                   w_reuse=w_reuse, data=data)

            def run(u, p, *lv):
                return run_ensemble_kernel(
                    body, u, p, ts=saveat,
                    extras=([("broadcast", saveat)]
                            + [("table", leaf) for leaf in lv]),
                    lane_tile=lane_tile,
                    work_words=rosenbrock_work_words(
                        n, ps.shape[1], stages=rtab.stages,
                        w_reuse=bool(w_reuse)),
                    fixed_words=data_words(data))

            if sensitivity == "adjoint":
                return kernel_adjoint(run, lanes_run)(u0s, ps, *dleaves)
            return run(u0s, ps, *dleaves)

        return lanes_run(u0s, ps)

    raise NotImplementedError(
        f"rosenbrock methods do not support ensemble={ensemble!r} "
        "(use 'vmap', 'array' or 'kernel')")


# ----------------------------------------------------------------------------
# family dispatch: sde (fixed-dt counter-RNG steppers, paper §5.2.2)
# ----------------------------------------------------------------------------

def _concrete_seed(seed):
    try:
        return int(seed)
    except (TypeError, jax.errors.TracerIntegerConversionError,
            jax.errors.ConcretizationTypeError):
        raise ValueError(
            "backend='pallas' specializes the RNG seed into the kernel; "
            "pass a concrete `seed=` (python int) outside of jit")


def _solve_sde(spec: MethodSpec, prob: SDEProblem, u0s, ps, *, ensemble,
               backend, t0, tf, dt0, saveat, n_steps, save_every, lane_tile,
               key, seed, noise_table, event, adaptive, rtol, atol, max_iters,
               lane_offset, brownian_depth, error_est, sensitivity=None,
               adjoint_steps=None, checkpoint_every=None, raw_prob=None):
    from .sde import (SDE_STEPPERS, default_bridge_depth, sde_event_state0,
                      sde_nf_per_step, sde_save_grid, sde_solve_adaptive,
                      sde_step_and_save, sde_step_save_event)

    # dataset plumbing mirrors _solve_erk: bound closures (f AND g) on the
    # XLA paths, raw callbacks + leaf arguments on the Pallas/adjoint ones
    data = getattr(raw_prob, "data", None)
    dleaves, dtreedef = data_flatten(data)

    if prob.noise not in spec.noise:
        raise ValueError(
            f"method {spec.name!r} supports noise {spec.noise}, "
            f"problem has {prob.noise!r}")
    if adaptive is None:
        adaptive = False  # family default: the paper's kernels are fixed-dt
    if adaptive and not spec.adaptive:
        raise ValueError(
            f"method {spec.name!r} has no adaptive step control; "
            "pass adaptive=False or pick an adaptive-capable stepper")
    if not adaptive and error_est is not None:
        raise ValueError(
            "error_est selects the adaptive SDE error estimator; it has no "
            "meaning for fixed-dt stepping (pass adaptive=True)")
    if seed is None:
        # keep the seed traceable (jit-able) on the XLA paths; the Pallas
        # kernel bakes it into the kernel closure and concretizes below
        seed = jnp.asarray(key)[-1] if key is not None else 0
    N, n = u0s.shape
    m = prob.noise_dim()
    stepper = SDE_STEPPERS[spec.name]
    nf_per_step = sde_nf_per_step(spec.name)

    # ---- adaptive: embedded-pair / step-doubling error + Brownian tree ----
    if adaptive:
        if noise_table is not None:
            raise NotImplementedError(
                "adaptive SDE draws from the virtual Brownian tree; "
                "noise_table injection is fixed-dt only")
        # estimator resolution: the registered embedded pair is the default
        # wherever it applies (diagonal noise); doubling everywhere else, and
        # always available explicitly for A/B comparison.
        if error_est is None:
            error_est = ("embedded"
                         if ("embedded" in spec.error_est
                             and prob.noise == "diagonal") else "doubling")
        if error_est not in spec.error_est:
            raise ValueError(
                f"method {spec.name!r} supports error_est {spec.error_est}, "
                f"got {error_est!r}")
        if error_est == "embedded" and prob.noise != "diagonal":
            raise ValueError(
                "embedded SDE pairs are diagonal-noise only (Levy-area-free "
                "estimators); pass error_est='doubling' for general noise")
        pair = spec.embedded if error_est == "embedded" else None
        est_order = (pair.est_order if pair is not None
                     else max(1, int(round(spec.order))))
        nf_att = (pair.nf_per_attempt if pair is not None
                  else 3 * nf_per_step)
        depth = (brownian_depth if brownian_depth is not None
                 else default_bridge_depth(t0, tf, dt0))
        if saveat is None:
            saveat = [tf]
        saveat = jnp.asarray(saveat, u0s.dtype)
        bounded, _ = _resolve_adjoint(sensitivity, True, adjoint_steps, None)
        kw = dict(seed=seed, m_noise=m, saveat=saveat, rtol=rtol, atol=atol,
                  max_iters=max_iters, event=event, depth=depth,
                  order=spec.order, nf_per_step=nf_per_step,
                  error_est=error_est,
                  embedded=pair.fn if pair is not None else None,
                  est_order=est_order, nf_per_attempt=nf_att,
                  bounded_steps=bounded, checkpoint_every=checkpoint_every)

        if ensemble == "vmap":
            def one(u0, p, lane):
                res = sde_solve_adaptive(prob.f, prob.g, stepper, prob.noise,
                                         u0, p, t0, tf, dt0, lane_idx=lane,
                                         lanes=False, **kw)
                if event is not None:
                    res, _ = res
                return res

            lanes_ix = (jnp.arange(N, dtype=jnp.uint32)
                        + jnp.asarray(lane_offset, jnp.uint32))
            res = jax.vmap(one)(u0s, ps, lanes_ix)
            return EnsembleResult(ts=saveat, us=res.us, u_final=res.u_final,
                                  t_final=res.t_final, naccept=res.naccept,
                                  nreject=res.nreject, nf=jnp.sum(res.nf),
                                  status=jnp.max(res.status))

        if ensemble in ("array", "kernel"):
            # "array": the whole ensemble as ONE lanes tile (one state
            # matrix); per-lane step control is kept so trajectories agree
            # bitwise with the vmap/kernel strategies.
            tile_n = N if ensemble == "array" else (lane_tile or XLA_LANE_TILE)

            def lanes_run(u, p, *lv):
                if lv:
                    bp = bind_problem_data(raw_prob,
                                           data_unflatten(dtreedef, lv))
                    f_loc, g_loc = bp.f, bp.g
                else:
                    f_loc, g_loc = prob.f, prob.g
                u0p, psp, T, B = _tile_lanes(u, p, tile_n)
                lanes_all = ((jnp.arange(T * B, dtype=jnp.uint32)
                              + jnp.asarray(lane_offset, jnp.uint32))
                             .reshape(T, B))

                def tile(args):
                    u0t, pt, lt = args
                    res = sde_solve_adaptive(f_loc, g_loc, stepper,
                                             prob.noise, u0t.T, pt.T, t0, tf,
                                             dt0, lane_idx=lt, lanes=True,
                                             **kw)
                    if event is not None:
                        res, _ = res
                    return res

                return _untile(jax.lax.map(tile, (u0p, psp, lanes_all)), N, n)

            if ensemble == "kernel" and backend == "pallas":
                from repro.kernels.ensemble_kernel import (kernel_adjoint,
                                                           run_ensemble_kernel,
                                                           sde_adaptive_body,
                                                           sde_work_words)
                kf = raw_prob.f if data is not None else prob.f
                kg = raw_prob.g if data is not None else prob.g
                body = sde_adaptive_body(
                    kf, kg, stepper, prob.noise, t0=float(t0),
                    tf=float(tf), dt0=float(dt0), rtol=float(rtol),
                    atol=float(atol), max_iters=max_iters, m_noise=m,
                    seed=_concrete_seed(seed), depth=depth, order=spec.order,
                    nf_per_step=nf_per_step, event=event, error_est=error_est,
                    embedded=pair.fn if pair is not None else None,
                    est_order=est_order, nf_per_attempt=nf_att, data=data)
                off = jnp.asarray([lane_offset], jnp.uint32)

                def run(u, p, *lv):
                    return run_ensemble_kernel(
                        body, u, p, ts=saveat,
                        extras=([("broadcast", saveat), ("broadcast", off)]
                                + [("table", leaf) for leaf in lv]),
                        lane_tile=lane_tile,
                        work_words=2 * sde_work_words(n, ps.shape[1], m)
                        + 8 * m, fixed_words=data_words(data))

                if sensitivity == "adjoint":
                    return kernel_adjoint(run, lanes_run)(u0s, ps, *dleaves)
                return run(u0s, ps, *dleaves)

            return lanes_run(u0s, ps)

        raise NotImplementedError(
            f"sde methods do not support ensemble={ensemble!r} "
            "(use 'vmap', 'array' or 'kernel')")

    # ---- fixed-dt: the paper's counter-RNG kernels -------------------------
    if saveat is not None:
        raise NotImplementedError(
            "fixed-dt SDE snapshots land on the save_every grid (pass "
            "n_steps/save_every); use adaptive=True for saveat-grid output")
    if n_steps is None:
        n_steps = int(round((tf - t0) / dt0))
    assert n_steps % save_every == 0
    _, remat = _resolve_adjoint(sensitivity, False, adjoint_steps, n_steps)

    ts = sde_save_grid(t0, dt0, n_steps, save_every, u0s.dtype)

    def ref_run(u, p, *lv):
        # XLA lanes path replaying the kernel's exact Threefry counter stream
        # (global lane indices) — the Pallas oracle, bitwise on every backend.
        # "array" is the same lock-step state matrix over the WHOLE ensemble
        # (for fixed dt the §5.1 array semantics and per-lane stepping agree).
        # `*lv` = dataset leaves when replaying for the data-driven adjoint.
        from repro.kernels.em.ref import ref_solve
        bp = (bind_problem_data(raw_prob, data_unflatten(dtreedef, lv))
              if lv else prob)
        us, uf, estate = ref_solve(bp, u, p, t0=t0, dt=dt0,
                                   n_steps=n_steps, method=spec.name,
                                   save_every=save_every, seed=seed,
                                   noise_table=noise_table, event=event,
                                   lane_offset=lane_offset, remat=remat,
                                   checkpoint_every=checkpoint_every)
        return _assemble_sde_result(ts, jnp.moveaxis(us, -1, 0), uf.T, N,
                                    n_steps, nf_per_step, t0, dt0, u0s.dtype,
                                    estate, lane_tiled=True)

    if ensemble == "kernel" and backend == "pallas":
        from repro.kernels.em.ops import solve_sde_ensemble_kernel
        kprob = raw_prob if data is not None else prob

        def run(u, p, *lv):
            d = data_unflatten(dtreedef, lv) if data is not None else None
            return solve_sde_ensemble_kernel(
                kprob, u, p, t0=t0, dt=dt0, n_steps=n_steps,
                method=spec.name, save_every=save_every, lane_tile=lane_tile,
                seed=_concrete_seed(seed), noise_table=noise_table,
                event=event, lane_offset=lane_offset, data=d)

        if sensitivity == "adjoint":
            from repro.kernels.ensemble_kernel import kernel_adjoint
            return kernel_adjoint(run, ref_run)(u0s, ps, *dleaves)
        return run(u0s, ps, *dleaves)

    if ensemble in ("array", "kernel"):
        return ref_run(u0s, ps)

    if ensemble == "vmap":
        from repro.kernels.rng import counter_normals_threefry

        if remat:
            from .loops import checkpointed_fori
            loop = partial(checkpointed_fori, checkpoint_every=checkpoint_every)
        else:
            loop = jax.lax.fori_loop

        def one(u0, p, lane, table_col):
            lane_v = jnp.full((m,), lane, jnp.uint32)
            rows = jnp.arange(m, dtype=jnp.uint32)
            S = n_steps // save_every

            def noise_fn(k, udtype):
                if noise_table is not None:
                    return jax.lax.dynamic_slice(
                        table_col, (k, 0), (1, m))[0].astype(udtype)
                return counter_normals_threefry(seed, k, lane_v, rows, udtype)

            us0 = jnp.zeros((S, n), u0.dtype)
            if event is None:
                def step(k, carry):
                    u, us = carry
                    return sde_step_and_save(
                        stepper, prob.f, prob.g, prob.noise, u, us, p, t0,
                        dt0, k, noise_fn(k, u.dtype), save_every)

                return loop(0, n_steps, step, (u0, us0)) + (None,)

            def step(k, carry):
                u, us, estate = carry
                return sde_step_save_event(
                    stepper, prob.f, prob.g, prob.noise, event, u, us, estate,
                    p, t0, dt0, k, noise_fn(k, u.dtype), save_every)

            estate0 = sde_event_state0((), t0, u0.dtype)
            return loop(0, n_steps, step, (u0, us0, estate0))

        lanes = (jnp.arange(N, dtype=jnp.uint32)
                 + jnp.asarray(lane_offset, jnp.uint32))
        if noise_table is not None:
            table_cols = jnp.moveaxis(noise_table, -1, 0)    # (N, steps, m)
            uf, us, estate = jax.vmap(one)(u0s, ps, lanes, table_cols)
        else:
            uf, us, estate = jax.vmap(
                partial(one, table_col=None))(u0s, ps, lanes)
        return _assemble_sde_result(ts, us, uf, N, n_steps, nf_per_step,
                                    t0, dt0, u0s.dtype, estate)

    raise NotImplementedError(
        f"sde methods do not support ensemble={ensemble!r} "
        "(use 'vmap', 'array' or 'kernel')")


def _assemble_sde_result(ts, us, uf, N, n_steps, nf_per_step, t0, dt,
                         dtype, estate=None,
                         lane_tiled=False) -> EnsembleResult:
    # lane_tiled: the XLA twin of the kernel, one fori loop of n_steps over
    # every lane, reports that trip count as each lane's steps_run
    if estate is None:
        t_final = jnp.full((N,), t0 + n_steps * dt, dtype)
        naccept = jnp.full((N,), n_steps, jnp.int32)
    else:
        # terminal events freeze lanes early: report the true per-lane step
        # count and the located event time, not the nominal grid end
        t_final = jnp.broadcast_to(estate["t_out"], (N,)).astype(dtype)
        naccept = jnp.broadcast_to(estate["naccept"], (N,))
    return EnsembleResult(
        ts=ts, us=us, u_final=uf, t_final=t_final, naccept=naccept,
        nreject=jnp.zeros((N,), jnp.int32),
        nf=jnp.asarray(n_steps * nf_per_step * N),
        status=jnp.asarray(0, jnp.int32),
        steps_run=(jnp.full((N,), n_steps, jnp.int32) if lane_tiled
                   else None))


# ----------------------------------------------------------------------------
# resumable segment engine (continuous-batching substrate — repro.serve)
# ----------------------------------------------------------------------------

class ResumableEngine:
    """Fixed-shape slot stepper: ONE compiled program per (body, widths).

    Wraps a per-lane resume body (`repro.core.solvers.erk_resume_body` /
    `repro.core.sde.sde_resume_body`) in a bounded while segment over a
    B-wide carry whose per-lane constants (p, tf / n_steps, lane, ...) live
    IN the carry.  `step_segment(carry, refill_mask, refill)` first merges
    refill columns into the carry — a full-width ``jnp.where`` over the
    trailing lane axis, so the jitted program is independent of WHICH slots
    refill — then advances every active lane by at most `segment_steps`
    attempts.  Applying the body to a done lane is an exact no-op (dt = 0 /
    write-masked), so mixed-progress slots cost nothing but the lane; the
    serve layer harvests done lanes between segments and refills their slots
    from the request queue without ever recompiling.
    """

    def __init__(self, init_fn, body_fn, segment_steps: int = 64):
        self.segment_steps = int(segment_steps)
        K = jnp.asarray(self.segment_steps, jnp.int32)

        def cond(c):
            return (c["iters"] < K) & jnp.any(~c["done"])

        def _segment(carry, refill_mask, refill):
            merged = {}
            for k, old in carry.items():
                if k == "iters":
                    # segment-local bound; per-request budgets are enforced
                    # host-side from naccept + nreject at harvest
                    merged[k] = jnp.asarray(0, jnp.int32)
                    continue
                m = refill_mask[None, :] if jnp.ndim(old) == 2 else refill_mask
                merged[k] = jnp.where(m, refill[k], old)
            return jax.lax.while_loop(cond, body_fn, merged)

        self._fresh = jax.jit(init_fn)
        self._segment = jax.jit(_segment)

    def fresh(self, *args):
        """Build a full-width carry (every column a fresh lane).  Used both
        for the initial pool state and — masked through `step_segment` — to
        stage refill columns: non-refilled columns are computed on filler
        values and discarded by the merge."""
        return self._fresh(*args)

    def step_segment(self, carry, refill_mask, refill):
        """Merge `refill` columns where `refill_mask` is set, then run one
        bounded segment.  `refill_mask` all-False (with `refill=carry`) is a
        pure advance."""
        return self._segment(carry, refill_mask, refill)

    def export_carry(self, carry):
        """Host-gather a carry for snapshotting (see `export_resume_carry`)."""
        return export_resume_carry(carry)

    def import_carry(self, host_carry):
        """Re-device a host carry exported by `export_carry`."""
        return import_resume_carry(host_carry)


def export_resume_carry(carry) -> dict:
    """Host-gather a resumable carry into plain numpy (dtype-preserving).

    The carry is the COMPLETE per-lane solver state — u, t, dt, counters,
    per-lane constants (p, tf / n_steps, lane index), done/status flags —
    so an exported carry is a restart point: re-devicing it and continuing
    with the same engine replays exactly the remaining body applications.
    This is what `repro.dist.elastic` snapshots through `checkpoint/ckpt.py`
    (host-gathered, so restore may re-shard onto any new mesh shape).
    """
    host = jax.device_get(carry)
    return {k: np.asarray(v) for k, v in host.items()}


def import_resume_carry(host_carry: dict):
    """Inverse of `export_resume_carry`: numpy host carry -> device arrays.
    Dtypes are preserved verbatim (bitwise-resume depends on it)."""
    return {k: jnp.asarray(v) for k, v in host_carry.items()}


def make_resumable_engine(spec: MethodSpec, prob, *, adaptive=None,
                          rtol=1e-6, atol=1e-6, event=None, seed=0,
                          m_noise=None, segment_steps: int = 64):
    """Build the (init, body) pair for a resumable method and wrap it in a
    `ResumableEngine`.

    erk:  ``engine.fresh(u0, p, t0, tf, dt0)`` — u0 (n, B), p (k, B), rest
          scalars or (B,).  The body is `solve_adaptive`'s own loop body
          (shared `_make_adaptive_body`) with p/tf carry-resident.
    sde (fixed-dt): ``engine.fresh(u0, p, t0, dt, n_steps, lane)`` — per-lane
          step counts and GLOBAL lane indices; noise replays the same
          (seed; step, lane, row) Threefry counters as the fresh kernels.

    Raises ValueError for non-resumable methods (`MethodSpec.resumable` is
    False — e.g. rosenbrock's lazy-W refresh gates are batch-reduced
    predicates that couple lanes): the serve layer runs those as coalesced
    one-shot batches instead (`repro.serve.slots.BatchPool`).
    """
    if not spec.resumable:
        raise ValueError(
            f"method {spec.name!r} declares resumable=False; serve it via "
            "coalesced one-shot batches (repro.serve.slots.BatchPool)")
    if spec.family == "sde":
        from .sde import sde_resume_body, sde_resume_init
        if adaptive:
            raise ValueError(
                "adaptive SDE stepping is not slot-resumable (Brownian-tree "
                "left-endpoint state is dt-path dependent); fixed-dt only")
        if m_noise is None:
            m_noise = prob.noise_dim()
        body = sde_resume_body(prob.f, prob.g, spec.name, prob.noise,
                               m_noise, seed, event=event)
        return ResumableEngine(sde_resume_init, body, segment_steps)
    if spec.family == "erk":
        from .solvers import erk_resume_body, erk_resume_init
        tab = spec.tableau
        if adaptive is None:
            adaptive = spec.adaptive
        opts = AdaptiveOptions(rtol=rtol, atol=atol, adaptive=adaptive)
        body = erk_resume_body(prob.f, tab, opts, event=event)
        init = partial(erk_resume_init, prob.f, tab)
        return ResumableEngine(init, body, segment_steps)
    raise ValueError(f"no resumable engine for family {spec.family!r}")


# ----------------------------------------------------------------------------
# front door
# ----------------------------------------------------------------------------

def solve_ensemble_local(eprob: EnsembleProblem, alg="tsit5",
                         ensemble: str = "kernel", backend: str = "xla",
                         t0=None, tf=None, dt0=1e-2, saveat=None,
                         rtol=1e-6, atol=1e-6, adaptive=None,
                         n_steps=None, save_every=1, lane_tile=None,
                         max_iters=100_000, event=None, key=None, seed=None,
                         noise_table=None, linsolve="jnp", lane_offset=0,
                         brownian_depth=None, error_est=None,
                         w_reuse=None, sensitivity=None, adjoint_steps=None,
                         checkpoint_every=None) -> EnsembleResult:
    """Single-device ensemble solve — ANY registered method through ANY
    strategy and backend (the unified front door; see docs/architecture.md).

    Args:
      eprob: `EnsembleProblem` wrapping an ODEProblem or SDEProblem with the
        per-trajectory (u0s, ps) variations materialized.  A problem with a
        dataset (``prob.data`` — tables consumed by 4-arg callbacks
        ``f(u, p, t, data)``; the texture-memory analog) dispatches through
        every strategy/backend below identically: XLA paths bind the tables
        over the callbacks, the Pallas kernels hold one VMEM-resident copy
        per lane tile (broadcast BlockSpec, footprint charged to the §5.2
        budget), and ``sensitivity="adjoint"`` reaches the table values
        (forcing-curve calibration) — see docs/architecture.md
        "Data-driven RHS".
      alg: a registry name (``"tsit5"``, ``"rosenbrock23"``, ``"em"``, ...),
        a `MethodSpec`, or a bare `Tableau` (auto-wrapped as an erk method).
      ensemble: execution strategy — ``"vmap"`` (per-trajectory baseline),
        ``"array"`` (one ensemble state matrix, paper §5.1),
        ``"array_eager"`` (un-jitted dispatch-overhead reproduction, erk
        only), ``"kernel"`` (fused whole-integration tiles, paper §5.2) or
        ``"auto"`` — measured dispatch: `repro.core.autotune` picks
        strategy/backend/lane_tile from the persisted profile cache, timing
        the capability-pruned candidates on this problem on first sight
        (see docs/architecture.md, "Autotuned dispatch").
      backend: ``"xla"`` (fused lax loops) or ``"pallas"`` (the generic
        ensemble Pallas kernel) — kernel strategy only.
      t0, tf, dt0: time span (defaults from ``prob.tspan``) and initial step.
        ``dt0=None`` (erk/rosenbrock only) derives the initial step from
        Hairer's two-evaluation heuristic (`repro.core.controller.initial_dt`)
        per trajectory, takes the ensemble minimum, and — unlike naive
        auto-dt wiring — COUNTS the 2·N probe RHS evaluations in the
        returned ``nf`` so work-precision sweeps stay honest.
      saveat: snapshot time grid (S,). Adaptive paths interpolate dense
        output onto it; fixed-dt SDE uses ``n_steps``/``save_every`` instead.
      rtol, atol: adaptive error-control tolerances.
      adaptive: None picks the family default (erk/rosenbrock: embedded
        adaptive stepping; sde: the paper's fixed-dt kernels).  Explicit
        ``True`` on an SDE method enables adaptive error control with
        rejection-safe virtual-Brownian-tree noise; explicit ``False`` forces
        fixed-dt stepping.
      error_est: adaptive-SDE error estimator — ``"embedded"`` (the method's
        registered embedded pair: one stepper pass + companion difference,
        ~2x cheaper per attempt) or ``"doubling"`` (step doubling: any
        stepper, general noise, 3x stepper cost).  None picks the embedded
        pair where one ships and the noise is diagonal, doubling otherwise.
        Both estimators draw from the same Brownian tree, so either choice
        is bitwise-reproducible across every strategy/backend/shard.
      n_steps, save_every: fixed-dt step count and snapshot stride.
      lane_tile: trajectories per fused tile (kernel strategy).  None derives
        the Pallas tile from the §5.2 VMEM formula (see docs/kernels.md).
      max_iters: adaptive-loop iteration cap (status=1 when exhausted).
      event: `repro.core.events.Event` — zero-crossing detection, bisection
        refinement and per-lane termination on EVERY family/strategy/backend.
      key, seed: SDE noise stream key — the same (seed; step, row, lane)
        Threefry stream is replayed on every strategy/backend, so SDE paths
        agree bitwise across dispatch targets.
      noise_table: optional pre-drawn (n_steps, m, N) N(0,1) table (fixed-dt
        SDE only), bypassing the counter RNG.
      linsolve: Rosenbrock W-solve mode ("jnp" | "pallas" | "lanes").
      w_reuse: Rosenbrock lazy-W control — ``None`` takes the method's
        `MethodSpec.w_reuse` default, ``False`` forces today's eager
        every-step Jacobian + factorization (bitwise-identical to the
        pre-lazy engine), ``True`` enables the default
        `repro.core.controller.WReusePolicy`, and a `WReusePolicy` instance
        customizes the freshness thresholds.  Reuse-on trajectories satisfy
        the same cross-strategy/backend parity contract; `njac`/`nfact`
        report the (much smaller) linear-algebra work.  The refresh is an
        any()-gated `lax.cond` on every strategy — the vmap path binds an
        axis name and psum-reduces the gate to an ensemble-uniform
        predicate, so the cond survives vmap batching as a real branch and
        the savings are wall time everywhere, not just counted work.
      lane_offset: GLOBAL index of this shard's first trajectory — keeps
        counter-RNG streams disjoint when `repro.core.api.solve_ensemble`
        splits an SDE ensemble over a mesh.  Local solves leave it 0.
      brownian_depth: dyadic resolution of the adaptive-SDE Brownian tree
        (default: `repro.core.sde.default_bridge_depth`).
      sensitivity: gradient capability (docs/architecture.md, "Gradients").
        ``None`` keeps the while-loop hot paths untouched.  ``"forward"``
        validates that forward-mode (jvp) sensitivities flow — they ride the
        while-loop engines as-is (XLA strategies only; the Pallas kernels
        have no jvp rule).  ``"adjoint"`` swaps the adaptive loops for the
        bounded, checkpointed reverse-differentiable substitute
        (`repro.core.loops.solver_loop`) so ``jax.grad``/``jax.vjp`` work
        through the solve: same accept/reject sequence, states agree with
        the while path to ulp, O(sqrt-steps) adjoint memory.  On
        ``backend="pallas"`` the forward solve still runs the fused kernel;
        a `jax.custom_vjp` on the kernel boundary replays the bitwise XLA
        twin under the bounded loop for the reverse pass.  Gradients flow
        through ``us``/``u_final`` w.r.t. (u0s, ps); solver statistics and
        event times are non-differentiable outputs.  SDE solves get pathwise
        gradients (the counter-RNG noise replays bitwise under vjp
        recomputation).
      adjoint_steps: static bound on the adaptive attempt count for
        ``sensitivity="adjoint"`` (required for adaptive stepping: probe the
        forward solve and use ``naccept + nreject`` plus margin; too small a
        bound reports ``status == 1``).  Fixed-dt paths derive it.
      checkpoint_every: steps per remat segment of the bounded adjoint loop
        (default sqrt(adjoint_steps) — `repro.core.loops`).

    Returns:
      `EnsembleResult` with trajectory-major ``us (N, S, n)``, per-trajectory
      final states/times and step statistics.  Terminal events record the
      located event time in ``t_final``.
    """
    spec = get_method(alg)
    prob = eprob.prob
    u0s, ps = eprob.materialize()
    t0 = prob.tspan[0] if t0 is None else t0
    tf = prob.tspan[1] if tf is None else tf

    # data-driven RHS (`prob.data`, the texture-memory analog): a capability
    # like events/w_reuse/sensitivity.  Validate it against the method, then
    # bind the dataset over the callbacks once — every XLA path downstream
    # sees a plain 3-arg problem; the Pallas branches receive `raw_prob`
    # (4-arg callbacks) and pass the table leaves as real kernel arguments.
    raw_prob = prob
    if getattr(prob, "data", None) is not None:
        if not spec.data_rhs:
            raise ValueError(
                f"method {spec.name!r} declares data_rhs=False; its engines "
                "cannot consume data-driven problems (prob.data)")
        prob = bind_problem_data(prob)

    if ensemble == "auto":
        # measured dispatch (repro.core.autotune): profile-cache hit or a
        # one-off micro-benchmark of the capability-pruned candidate set on
        # this very problem; near-zero overhead once the cache is warm.
        from .autotune import resolve_auto
        dec = resolve_auto(eprob, spec, t0=t0, tf=tf, dt0=dt0, saveat=saveat,
                           rtol=rtol, atol=atol, adaptive=adaptive,
                           n_steps=n_steps, save_every=save_every,
                           max_iters=max_iters, event=event, key=key,
                           seed=seed, noise_table=noise_table,
                           error_est=error_est, w_reuse=w_reuse,
                           linsolve=linsolve, sensitivity=sensitivity)
        ensemble, backend = dec.strategy, dec.backend
        if lane_tile is None:
            lane_tile = dec.lane_tile   # an explicit user tile always wins

    if event is not None and not spec.events:
        raise ValueError(
            f"method {spec.name!r} declares events=False; pick a method whose "
            "MethodSpec supports event handling")

    if sensitivity is not None:
        # same rules as methods.valid_dispatch(sensitivity=...) — kept in
        # sync so the autotuner prunes exactly what would raise here
        if sensitivity not in ("forward", "adjoint"):
            raise ValueError(f"unknown sensitivity {sensitivity!r} "
                             "(use 'forward' or 'adjoint')")
        if sensitivity not in spec.sensitivity:
            raise ValueError(
                f"method {spec.name!r} declares differentiable=False; its "
                "engines do not satisfy the AD contract "
                "(docs/adding-a-method.md)")
        if ensemble == "array_eager":
            raise ValueError(
                "sensitivity through ensemble='array_eager' is not possible: "
                "the eager loop is host-driven python, not traceable")
        if sensitivity == "forward" and backend == "pallas":
            raise ValueError(
                "forward sensitivities ride jvp through the while-loop "
                "engines; the Pallas kernels support sensitivity='adjoint' "
                "(custom_vjp boundary) only — use backend='xla' for jvp")

    if w_reuse and spec.family != "rosenbrock":
        # only a truthy request is an error: w_reuse=False/None stays the
        # documented universal no-op, so generic sweeps can pass it blindly
        raise ValueError(
            "w_reuse controls the Rosenbrock lazy-W hot path; "
            f"{spec.name!r} ({spec.family}) has no W = I − γh·J to reuse")

    auto_dt_nf = 0
    if dt0 is None:
        # Hairer auto-dt: two probe f evaluations PER TRAJECTORY, charged to
        # nf below so auto-dt runs stop flattering work-precision plots
        if spec.family == "sde":
            raise ValueError(
                "dt0=None (automatic initial step) is erk/rosenbrock only; "
                "SDE stepping needs an explicit dt0")
        from .controller import initial_dt
        order = max(1, int(round(spec.order)))
        h = jax.vmap(lambda u0, pp: initial_dt(prob.f, u0, pp, t0, tf, order,
                                               atol, rtol))(u0s, ps)
        dt0 = jnp.min(h)
        if backend == "pallas":
            # the fused kernel bakes dt0 into its closure (same constraint
            # as t0/tf/seed) — surface the jit limitation clearly instead of
            # crashing at float() deep inside the kernel factory
            try:
                dt0 = float(dt0)
            except jax.errors.ConcretizationTypeError:
                raise ValueError(
                    "dt0=None with backend='pallas' requires eager dispatch "
                    "(the kernel closure specializes dt0, like t0/tf/seed); "
                    "compute initial_dt outside jit or use backend='xla'")
        auto_dt_nf = 2 * u0s.shape[0]

    # the kernel family's XLA twin traces table lookups the way the Pallas
    # body must (masked sums, no 1-D gather), so the two backends stay
    # bitwise twins; vmap and array keep the O(1) gather
    with kernel_lookups(ensemble == "kernel"):
        if spec.family == "sde":
            if not isinstance(prob, SDEProblem):
                raise TypeError(
                    f"method {spec.name!r} is an SDE stepper but the "
                    f"problem is {type(prob).__name__}")
            return _solve_sde(spec, prob, u0s, ps, ensemble=ensemble,
                              backend=backend, t0=t0, tf=tf, dt0=dt0,
                              saveat=saveat, n_steps=n_steps,
                              save_every=save_every, lane_tile=lane_tile,
                              key=key, seed=seed, noise_table=noise_table,
                              event=event,
                              adaptive=adaptive, rtol=rtol, atol=atol,
                              max_iters=max_iters, lane_offset=lane_offset,
                              brownian_depth=brownian_depth,
                              error_est=error_est,
                              sensitivity=sensitivity,
                              adjoint_steps=adjoint_steps,
                              checkpoint_every=checkpoint_every,
                              raw_prob=raw_prob)

        if error_est is not None:
            raise ValueError(
                "error_est selects the adaptive SDE error estimator; "
                f"{spec.name!r} ({spec.family}) embeds via its tableau")

        if isinstance(prob, SDEProblem):
            raise TypeError(
                f"problem {prob.name!r} is stochastic; pick an sde method "
                f"(e.g. alg='em'), not {spec.name!r}")

        if spec.family == "rosenbrock":
            res = _solve_rosenbrock(spec, prob, u0s, ps, ensemble=ensemble,
                                    backend=backend, t0=t0, tf=tf, dt0=dt0,
                                    saveat=saveat, rtol=rtol, atol=atol,
                                    lane_tile=lane_tile, max_iters=max_iters,
                                    linsolve=linsolve, event=event,
                                    w_reuse=w_reuse, sensitivity=sensitivity,
                                    adjoint_steps=adjoint_steps,
                                    checkpoint_every=checkpoint_every,
                                    raw_prob=raw_prob)
        else:
            res = _solve_erk(spec, prob, u0s, ps, ensemble=ensemble,
                             backend=backend, t0=t0, tf=tf, dt0=dt0,
                             saveat=saveat, rtol=rtol, atol=atol,
                             adaptive=adaptive, n_steps=n_steps,
                             save_every=save_every, lane_tile=lane_tile,
                             max_iters=max_iters, event=event,
                             sensitivity=sensitivity,
                             adjoint_steps=adjoint_steps,
                             checkpoint_every=checkpoint_every,
                             raw_prob=raw_prob)
    if auto_dt_nf:
        res = res._replace(nf=res.nf + auto_dt_nf)
    return res
