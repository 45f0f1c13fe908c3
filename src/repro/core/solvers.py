"""Generic explicit Runge-Kutta engine (tableau-driven), three execution shapes.

One engine serves every strategy in the paper:

  * scalar mode   — ``u: (n,)``, scalar ``t/dt``: the per-trajectory reference
                    solver (`solve_one`); `vmap`-ing it reproduces the JAX/Diffrax
                    baseline the paper benchmarks against (EnsembleVmap).
  * array mode    — ``u: (N, n)``, scalar ``t/dt`` and an ensemble-wide error
                    norm: bitwise-faithful EnsembleGPUArray semantics (§5.1) —
                    one lock-step dt for the whole ensemble.
  * lanes mode    — ``u: (n, B)``, per-lane ``t/dt/accept`` masks: the structure
                    of the paper's EnsembleGPUKernel (§5.2) adapted to TPU vector
                    lanes; this exact loop body is also what the Pallas kernel
                    runs per tile (kernels/tsit5).

All of it is pure ``jax.lax`` control flow (while_loop / scan / cond) — no
Python-level stepping — so each solve lowers to a single XLA computation
("one kernel launch" in the paper's terms).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .controller import (STATUS_DTMIN_EXHAUSTED, PIController, hairer_norm,
                         pi_propose)
from .events import Event, handle_event
from .loops import checkpointed_fori, solver_loop
from .tableaus import Tableau

Array = Any


class SolveResult(NamedTuple):
    ts: Array        # (S,) save times (the common saveat grid)
    us: Array        # scalar/array mode: (S, n)/(S, N, n); lanes: (S, n, B)
    t_final: Array
    u_final: Array
    naccept: Array
    nreject: Array
    status: Array    # 0 = success, 1 = max_iters exhausted,
    #                  2 = dt pinned at dtmin while rejecting (see
    #                  repro.core.controller.STATUS_DTMIN_EXHAUSTED)
    nf: Array        # number of RHS evaluations (per control element)
    njac: Array = 0  # Jacobian evaluations (stiff family; 0 elsewhere)
    nfact: Array = 0  # W = I − γh·J factorizations (stiff family)
    iters: Array = 0  # trip count of the integration loop: in lanes mode
    #                   the tile's, at least every lane's naccept + nreject


# ----------------------------------------------------------------------------
# single embedded RK step
# ----------------------------------------------------------------------------

def _bc(v, u):
    """Broadcast a control value (scalar or (B,)) against state u ((n,)/(N,n)/(n,B))."""
    return v if jnp.ndim(v) == 0 else v[None]


def rk_step(f, tab: Tableau, u, p, t, dt, k1):
    """One embedded step. Returns (u_new, err, ks).

    k1 must be f(u, p, t) (caller owns FSAL reuse). The stage loop is a static
    Python unroll — 6-16 fused vector ops, no dynamic control flow.
    """
    s = tab.stages
    dtb = _bc(dt, u)
    ks = [k1]
    # NOTE: tableau entries are converted to python floats (weak-typed) so the
    # state dtype (f32 on accelerators, f64 reference) is never upcast.
    for i in range(1, s):
        acc = None
        for j in range(i):
            aij = float(tab.a[i, j])
            if aij == 0.0:
                continue
            term = aij * ks[j]
            acc = term if acc is None else acc + term
        ui = u if acc is None else u + dtb * acc
        ks.append(f(ui, p, t + float(tab.c[i]) * dt))
    unew_acc = None
    err_acc = None
    for i in range(s):
        if tab.b[i] != 0.0:
            term = float(tab.b[i]) * ks[i]
            unew_acc = term if unew_acc is None else unew_acc + term
        if tab.btilde[i] != 0.0:
            term = float(tab.btilde[i]) * ks[i]
            err_acc = term if err_acc is None else err_acc + term
    u_new = u + dtb * unew_acc
    err = dtb * err_acc if err_acc is not None else jnp.zeros_like(u)
    return u_new, err, ks


def interp_step(f, tab: Tableau, u_old, u_new, ks, p, t, dt, theta,
                lanes: bool = False):
    """Dense output u(t + theta*dt), theta in [0,1].

    Uses the tableau's free interpolant when available (Tsit5: 4th order),
    otherwise cubic Hermite on (u_old, k1, u_new, f(u_new)).

    Shape contract:
      lanes=False: u (n,)/(N,n), dt scalar, theta scalar or (S,)
                   -> u-shaped or (S, *ushape).
      lanes=True : u (n,B), dt (B,), theta (B,) or (S,B) — the LAST theta axis
                   is the lane axis -> (n,B) or (S,n,B).
    """
    th_nd = jnp.ndim(theta)
    u_nd = jnp.ndim(u_old)

    def expand_w(w):
        """Align a (*theta.shape) weight against the state axes."""
        if th_nd == 0:
            return w
        if lanes:
            # (..., B) -> (..., 1, B); state (n, B) broadcasts in.
            return jnp.expand_dims(w, axis=-2)
        return w.reshape(jnp.shape(w) + (1,) * u_nd)

    def expand_u(x):
        """Align a state against leading (non-lane) theta axes."""
        lead = th_nd - (1 if lanes else 0)
        if lead <= 0:
            return x
        return x.reshape((1,) * lead + jnp.shape(x))

    dtb = _bc(dt, u_old)  # scalar or (1, B)

    if tab.interp_bpoly is not None:
        bw = tab.interp_bpoly(theta)          # (s, *theta.shape)
        incr = None
        for i, k in enumerate(ks):
            term = expand_w(bw[i]) * expand_u(k)
            incr = term if incr is None else incr + term
        return expand_u(u_old) + dtb * incr
    # Hermite cubic
    f_old = ks[0]
    f_new = ks[-1] if tab.fsal else f(u_new, p, t + dt)
    the = theta
    h00 = expand_w((1 + 2 * the) * (1 - the) ** 2)
    h10 = expand_w(the * (1 - the) ** 2)
    h01 = expand_w(the ** 2 * (3 - 2 * the))
    h11 = expand_w(the ** 2 * (the - 1))
    return (h00 * expand_u(u_old) + h10 * dtb * expand_u(f_old)
            + h01 * expand_u(u_new) + h11 * dtb * expand_u(f_new))


# ----------------------------------------------------------------------------
# fixed-step fast path (scan): the throughput shape of the paper's kernels
# ----------------------------------------------------------------------------

def solve_fixed(f, tab: Tableau, u0, p, t0, dt, n_steps: int,
                save_every: int = 1, remat: bool = False,
                checkpoint_every: Optional[int] = None):
    """Fixed-dt integration as scan(fori(rk_step)). Differentiable (fwd+rev).

    Saves every `save_every`-th step => S = n_steps // save_every snapshots.
    Works for any state shape (scalar/array/lanes).  ``remat=True`` wraps each
    save chunk in `jax.checkpoint` and segments the chunk's step loop with
    `repro.core.loops.checkpointed_fori` (``checkpoint_every`` steps per
    segment, default sqrt(save_every)) — the primal is bitwise-unchanged, but
    the reverse pass stores one (u, t) carry per snapshot plus one per
    segment and recomputes stages inside segments, bounding adjoint memory at
    O(S + save_every/ck + ck) states instead of O(n_steps).
    """
    assert n_steps % save_every == 0, "n_steps must be divisible by save_every"
    S = n_steps // save_every
    dt = jnp.asarray(dt, dtype=u0.dtype)
    t0 = jnp.asarray(t0, dtype=u0.dtype)

    def inner(carry, _):
        u, t = carry

        def one(i, uk):
            u, t = uk
            k1 = f(u, p, t)
            u_new, _, _ = rk_step(f, tab, u, p, t, dt, k1)
            return (u_new, t + dt)

        if remat:
            u, t = checkpointed_fori(0, save_every, one, (u, t),
                                     checkpoint_every=checkpoint_every)
        else:
            u, t = jax.lax.fori_loop(0, save_every, one, (u, t))
        return (u, t), u

    if remat:
        inner = jax.checkpoint(inner)
    (u_f, t_f), us = jax.lax.scan(inner, (u0, t0), None, length=S)
    ts = t0 + dt * save_every * jnp.arange(1, S + 1, dtype=u0.dtype)
    nf = jnp.asarray(n_steps * (tab.stages - (1 if tab.fsal else 0)) + (1 if tab.fsal else 0))
    return SolveResult(ts=ts, us=us, t_final=t_f, u_final=u_f,
                       naccept=jnp.asarray(n_steps), nreject=jnp.asarray(0),
                       status=jnp.asarray(0), nf=nf,
                       iters=jnp.asarray(n_steps, jnp.int32))


# ----------------------------------------------------------------------------
# adaptive driver (while_loop), scalar/array/lanes via shape polymorphism
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AdaptiveOptions:
    rtol: float = 1e-6
    atol: float = 1e-6
    max_iters: int = 100_000
    controller: Optional[PIController] = None
    adaptive: bool = True            # False => accept every step at fixed dt
    save: str = "grid"               # "grid" | "final"
    norm_axes: Optional[Any] = "auto"  # "auto": lanes->0, else None
    # Reverse-mode AD (repro.core.loops / repro.core.sensitivity): replace the
    # while_loop with bounded_steps checkpointed scan segments and freeze the
    # step-size controller out of the autodiff graph (discrete adjoint of the
    # realized step sequence).  Whenever the bound covers the true iteration
    # count (too small => status == 1) the accept/step sequence is identical
    # to the while path; values agree to ulp (the adjoint-safe probe changes
    # XLA fusion, so exact bits may differ — see docs/architecture.md).
    bounded_steps: Optional[int] = None
    checkpoint_every: Optional[int] = None


def _grid_save(f, tab, us, saveat, u_old, u_new, ks, p, t_old, dt_step,
               t_new, active):
    """Masked write of every save point crossed by this step (vectorized over S).

    saveat: (S,). lanes mode: t_old/t_new (B,), us (S,n,B); scalar/array:
    t_old scalar, us (S,*ushape). Cost is O(S) vector ops but only paid on
    steps that cross a save point (guarded by lax.cond in the caller).
    """
    lanes = jnp.ndim(t_old) == 1
    eps = jnp.asarray(1e-7, us.dtype) * jnp.maximum(jnp.abs(t_new), 1.0)
    if lanes:
        cross = ((saveat[:, None] > t_old[None, :])
                 & (saveat[:, None] <= t_new[None, :] + eps[None, :])
                 & active[None, :])                       # (S, B)
        theta = jnp.clip((saveat[:, None] - t_old[None, :])
                         / jnp.where(dt_step[None, :] == 0, 1.0, dt_step[None, :]),
                         0.0, 1.0)                        # (S, B)
        vals = interp_step(f, tab, u_old, u_new, ks, p, t_old, dt_step, theta,
                           lanes=True)
        # vals: (S, n, B); cross -> (S, 1, B)
        return jnp.where(cross[:, None, :], vals, us)
    else:
        cross = ((saveat > t_old) & (saveat <= t_new + eps) & active)  # (S,)
        theta = jnp.clip((saveat - t_old) / jnp.where(dt_step == 0, 1.0, dt_step),
                         0.0, 1.0)
        vals = interp_step(f, tab, u_old, u_new, ks, p, t_old, dt_step, theta)
        cross_e = cross.reshape(cross.shape + (1,) * (us.ndim - 1))
        return jnp.where(cross_e, vals, us)


def _nf_per_attempt(tab: Tableau, event) -> int:
    """RHS evaluations per attempted step: FSAL reuses the last stage,
    except after an event may have modified the state."""
    return tab.stages - 1 if (tab.fsal and event is None) else tab.stages


def _make_adaptive_body(f, tab: Tableau, opts: AdaptiveOptions, ctrl, event,
                        lanes: bool, dtype, cshape, axes, saveat, save_grid,
                        bounded, p=None, tf=None):
    """The adaptive loop body, shared by `solve_adaptive` (p/tf closed over)
    and the resumable segment engine (`erk_resume_body`: p/tf read from the
    carry, so every per-lane constant travels WITH the lane and a slot can be
    refilled with a different request's problem without recompiling).  In
    closure mode the emitted expressions are identical to the historical
    inline body — bitwise-stable refactor."""
    per_lane_consts = p is None

    def body(c):
        p_ = c["p"] if per_lane_consts else p
        tf_ = c["tf"] if per_lane_consts else tf
        t, u, dt, k1 = c["t"], c["u"], c["dt"], c["k1"]
        active = ~c["done"]
        remaining = tf_ - t
        dt_step = jnp.minimum(dt, remaining)
        # done lanes step at dt = 0: the stage cascade is an exact no-op on
        # them (any value is output-invariant — every write is accept-masked —
        # but a nonzero dt lets finished stiff lanes synthesize inf/NaN
        # candidates, which poisons the reverse pass via 0 * inf cotangents)
        dt_step = jnp.where(active, dt_step, jnp.asarray(0.0, dtype))

        u_cand, err, ks = rk_step(f, tab, u, p_, t, dt_step, k1)

        if opts.adaptive:
            enorm = hairer_norm(err, u, u_cand, opts.atol, opts.rtol, axes=axes)
            finite = jnp.isfinite(u_cand)
            if lanes:
                finite = jnp.all(finite, axis=0)
            else:
                finite = jnp.all(finite)
            accept = (enorm <= 1.0) & finite
            if bounded:
                # Frozen-step discrete adjoint: the controller chain (enorm ->
                # dt) is severed from the autodiff graph — we differentiate
                # the realized step sequence, not the step-size policy.  This
                # also keeps hairer_norm's sqrt out of the transposed graph.
                enorm = jax.lax.stop_gradient(enorm)
            dt_next, enorm_prev = pi_propose(ctrl, dt, enorm, c["enorm_prev"],
                                             accept)
        else:
            enorm = jnp.zeros(cshape, dtype)
            accept = jnp.ones(cshape, bool)
            dt_next, enorm_prev = dt, c["enorm_prev"]

        accept = accept & active
        dt_try = dt_step   # pre-adjoint-mask attempt size (dtmin-floor check)
        if bounded and opts.adaptive:
            # Adjoint-safe second pass: the first cascade above was a primal-
            # only probe (its only consumers are the frozen accept/controller
            # values); re-run it at where(accept, dt, 0) so the DIFFERENTIATED
            # stage cascade is an exact no-op on rejected attempts.  Accepted
            # lanes recompute bit-identical values; the reverse pass never
            # transposes an f evaluation at an off-trajectory (possibly
            # overflowed) rejected candidate.
            dt_step = jnp.where(accept, dt_step, jnp.asarray(0.0, dtype))
            u_cand, err, ks = rk_step(f, tab, u, p_, t, dt_step, k1)
        t_new = jnp.where(accept, t + dt_step, t)

        # ---- events: detect/locate/apply via the shared machinery ----------
        if event is not None:
            def interp_fn(theta):
                return interp_step(f, tab, u, u_cand, ks, p_, t, dt_step,
                                   theta, lanes=lanes)

            u_next, t_new, ev_t, ev_n, term = handle_event(
                event, interp_fn, u, u_cand, p_, t, dt_step, t_new, accept,
                c["event_t"], c["event_count"], lanes=lanes)
        else:
            u_next = u_cand
            ev_t, ev_n = c["event_t"], c["event_count"]
            term = jnp.zeros(cshape, bool)

        acc_e = _bc(accept, u) if lanes else accept
        u_new = jnp.where(acc_e, u_next, u)
        # FSAL: reuse last stage; recompute after an event modified the state
        if tab.fsal and event is None:
            k1_new = jnp.where(acc_e, ks[-1], k1)
        else:
            k1_new = jnp.where(acc_e, f(u_new, p_, t_new), k1)

        # ---- dense save -----------------------------------------------------
        if save_grid:
            def do_save(us):
                return _grid_save(f, tab, us, saveat, u, u_cand, ks, p_, t,
                                  dt_step, t_new, accept)

            any_cross = jnp.any(
                accept & (jnp.max(saveat) > (t.min() if lanes else t)))
            us = jax.lax.cond(any_cross, do_save, lambda x: x, c["us"])
        else:
            us = c.get("us")

        # dt pinned at the controller floor and still rejecting: retrying the
        # identical step is a deterministic live-lock — terminate the lane
        # with a distinct status instead of spinning to max_iters
        hopeless = active & ~accept & ~(dt_try > ctrl.dtmin) if opts.adaptive \
            else jnp.zeros(cshape, bool)
        statusv = jnp.where(hopeless,
                            jnp.asarray(STATUS_DTMIN_EXHAUSTED, jnp.int32),
                            c["status"])
        eps_end = 1e-7 * jnp.maximum(jnp.abs(tf_), 1.0)
        done = c["done"] | (t_new >= tf_ - eps_end) | term | hopeless

        out = dict(
            t=t_new, u=u_new, dt=dt_next, k1=k1_new,
            enorm_prev=enorm_prev, done=done,
            naccept=c["naccept"] + accept.astype(jnp.int32),
            nreject=c["nreject"] + (active & ~accept).astype(jnp.int32),
            status=statusv, iters=c["iters"] + 1,
            event_t=ev_t, event_count=ev_n,
        )
        if "nf" in c:
            # resume carries count RHS work as they go; solve_adaptive
            # derives nf from the attempt counters after the loop instead
            # (a carry fed only by `done` is a loop Mosaic cannot lay out)
            out["nf"] = c["nf"] + jnp.where(
                active, _nf_per_attempt(tab, event), 0).astype(jnp.int32)
        if us is not None:
            out["us"] = us
        if per_lane_consts:
            out["p"], out["tf"] = c["p"], c["tf"]
        return out

    return body


def solve_adaptive(f, tab: Tableau, u0, p, t0, tf, dt0,
                   saveat: Optional[Array] = None,
                   opts: AdaptiveOptions = AdaptiveOptions(),
                   event: Optional[Event] = None,
                   lanes: bool = False):
    """Adaptive (or fixed-accept) integration with optional events.

    lanes=False, u0 (n,)   : per-trajectory (scalar control).
    lanes=False, u0 (N, n) : EnsembleGPUArray lock-step semantics (scalar
                             control, ensemble-wide norm).
    lanes=True,  u0 (n, B) : per-lane control — EnsembleGPUKernel structure.
    """
    dtype = u0.dtype
    ctrl = opts.controller or PIController.for_order(tab.embedded_order)
    cshape = (u0.shape[-1],) if lanes else ()
    axes = (0 if lanes else None) if opts.norm_axes == "auto" else opts.norm_axes

    t0 = jnp.asarray(t0, dtype)
    tf = jnp.asarray(tf, dtype)
    tv = jnp.broadcast_to(t0, cshape).astype(dtype)
    dtv = jnp.broadcast_to(jnp.asarray(dt0, dtype), cshape).astype(dtype)

    if saveat is None:
        saveat = jnp.asarray([tf], dtype)
    saveat = jnp.asarray(saveat, dtype)
    S = saveat.shape[0]
    save_grid = opts.save == "grid"
    us0 = jnp.zeros((S,) + u0.shape, dtype)
    # prefill save points at/before t0 with u0
    # compare as an (S, 1) column: Mosaic cannot reshape an (S,) vector to
    # rank 3, but it can extend a column with unit dims
    pre = (saveat[:, None] <= t0).reshape((S,) + (1,) * u0.ndim)
    us0 = jnp.where(pre, u0[None], us0)

    k0 = f(u0, p, tv)
    carry0 = dict(
        t=tv, u=u0, dt=dtv, k1=k0,
        enorm_prev=jnp.ones(cshape, dtype),
        done=jnp.zeros(cshape, bool),
        us=us0,
        naccept=jnp.zeros(cshape, jnp.int32),
        nreject=jnp.zeros(cshape, jnp.int32),
        status=jnp.zeros(cshape, jnp.int32),
        iters=jnp.asarray(0, jnp.int32),
        event_t=jnp.full(cshape, jnp.inf, dtype),
        event_count=jnp.zeros(cshape, jnp.int32),
    )

    def cond(c):
        return (c["iters"] < opts.max_iters) & jnp.any(~c["done"])

    bounded = opts.bounded_steps is not None
    body = _make_adaptive_body(f, tab, opts, ctrl, event, lanes, dtype,
                               cshape, axes, saveat, save_grid, bounded,
                               p=p, tf=tf)
    out = solver_loop(cond, body, carry0, bounded_steps=opts.bounded_steps,
                      checkpoint_every=opts.checkpoint_every)
    status = jnp.where(out["status"] > 0, out["status"],
                       jnp.where(out["done"], 0, 1)).astype(jnp.int32)
    res = SolveResult(ts=saveat, us=out["us"], t_final=out["t"],
                      u_final=out["u"], naccept=out["naccept"],
                      nreject=out["nreject"], status=status,
                      nf=1 + _nf_per_attempt(tab, event)
                      * (out["naccept"] + out["nreject"]),
                      iters=out["iters"])
    if event is not None:
        return res, dict(event_t=out["event_t"], event_count=out["event_count"])
    return res


# ----------------------------------------------------------------------------
# resumable per-lane carry (the serving engine's substrate)
# ----------------------------------------------------------------------------

def erk_resume_init(f, tab: Tableau, u0, p, t0, tf, dt0):
    """Fresh per-lane resume carry — lanes mode only: u0 (n, B), p (k, B),
    t0/tf/dt0 scalars or (B,).

    Field-for-field identical to `solve_adaptive`'s initial carry minus the
    dense save buffer, plus carry-resident p/tf: a lane stepped to completion
    by `erk_resume_body` realizes the exact accept/step sequence of a fresh
    `solve_adaptive(..., lanes=True)` on the same column — bitwise (the loop
    body is the same shared `_make_adaptive_body`; per-lane control never
    couples lanes outside no-op iterations).
    """
    dtype = u0.dtype
    cshape = (u0.shape[-1],)
    tv = jnp.broadcast_to(jnp.asarray(t0, dtype), cshape).astype(dtype)
    tfv = jnp.broadcast_to(jnp.asarray(tf, dtype), cshape).astype(dtype)
    dtv = jnp.broadcast_to(jnp.asarray(dt0, dtype), cshape).astype(dtype)
    k0 = f(u0, p, tv)
    return dict(
        t=tv, u=u0, dt=dtv, k1=k0,
        enorm_prev=jnp.ones(cshape, dtype),
        done=jnp.zeros(cshape, bool),
        naccept=jnp.zeros(cshape, jnp.int32),
        nreject=jnp.zeros(cshape, jnp.int32),
        nf=jnp.ones(cshape, jnp.int32),
        status=jnp.zeros(cshape, jnp.int32),
        iters=jnp.asarray(0, jnp.int32),
        event_t=jnp.full(cshape, jnp.inf, dtype),
        event_count=jnp.zeros(cshape, jnp.int32),
        p=p, tf=tfv,
    )


def erk_resume_body(f, tab: Tableau, opts: AdaptiveOptions = AdaptiveOptions(),
                    event: Optional[Event] = None):
    """Build the per-lane resumable step body (lanes mode) over the carry from
    `erk_resume_init`: the exact `solve_adaptive` loop body with p/tf read
    from the carry instead of closed over, so ONE compiled body serves every
    request with this (method, n, dtype) signature — slot refill never
    recompiles.  Applying it to a done lane is an exact no-op (dt_step = 0,
    every write accept/active-masked), so mixed-progress slots are safe.
    No dense save buffer: serving returns final states + stats.
    """
    ctrl = opts.controller or PIController.for_order(tab.embedded_order)
    bounded = opts.bounded_steps is not None

    def body(c):
        dtype = c["u"].dtype
        cshape = (c["u"].shape[-1],)
        inner = _make_adaptive_body(f, tab, opts, ctrl, event, True, dtype,
                                    cshape, 0, None, False, bounded)
        return inner(c)

    return body


# ----------------------------------------------------------------------------
# public single-trajectory reference solver
# ----------------------------------------------------------------------------

def solve_one(f, tab: Tableau, u0, p, t0, tf, dt0, saveat=None,
              rtol=1e-6, atol=1e-6, adaptive=True, max_iters=100_000,
              event=None, save="grid", controller=None, bounded_steps=None,
              checkpoint_every=None):
    opts = AdaptiveOptions(rtol=rtol, atol=atol, max_iters=max_iters,
                           adaptive=adaptive, save=save, controller=controller,
                           bounded_steps=bounded_steps,
                           checkpoint_every=checkpoint_every)
    return solve_adaptive(f, tab, u0, p, t0, tf, dt0, saveat=saveat, opts=opts,
                          event=event, lanes=False)
