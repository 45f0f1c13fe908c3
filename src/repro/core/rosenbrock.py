"""Rosenbrock stiff ensemble engine — tableau-generic W-methods (paper §5.1.3).

The paper (§7) lists stiff ODEs as unsupported by EnsembleGPUKernel and
describes the enabling primitive (§5.1.3): the block-diagonal W = I - γh·J
solved as N independent small LU factorizations.  This module is the s-stage
generalization of that idea: ONE engine, driven by a `RosenbrockTableau`
(`repro.core.tableaus` — implementation-form γ, a, C, b, b̂, c, d), executes
Rosenbrock23 (2 effective stages), Rodas4 (6) and Rodas5P (8) — and any
future tableau that passes the Rosenbrock order-condition checker
(`repro.core.order_conditions`).

Per step the engine factors W = I − γh·J once and back-substitutes s times —
and with `w_reuse` (the lazy-W hot path) it goes further: J, the factored
LU(W) and the dt it was factored at ride the while_loop carry, refreshed per
lane only when the `WReusePolicy` freshness controller asks (rejection with a
reused J, accepted-error growth, γ-scaled dt drift, age), with an
extrapolated-secant rank-1 touch-up keeping the cached J honest in between
(`repro.core.controller.WReusePolicy`).  The stage solves are:

    g_i   = u + Σ_{j<i} a_ij U_j
    W U_i = γh f(g_i, t + c_i h) + γ Σ_{j<i} C_ij U_j + γ d_i h² f_t
    u1    = u + Σ b_i U_i,    err = Σ btilde_i U_i

The Jacobian comes from the analytic `jac(u, p, t)` hook when the problem
supplies one (`ODEProblem.jac`, threaded through MethodSpec dispatch) and
falls back to forward-mode AD (`jacfwd` — the "automated translation": users
never *have* to write Jacobians).  Linear solves go through the batched-LU
Pallas kernel in lanes mode (`linsolve="pallas"`), the kernel *body* inlined
for fused kernels (`"lanes"`), or vmapped LAPACK (`"jnp"`).

Shape-polymorphic like the RK engine: scalar mode u (n,), lanes mode u (n, B).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .controller import (STATUS_DTMIN_EXHAUSTED, PIController, WReusePolicy,
                         hairer_norm, pi_propose, w_dt_blame, w_mark_stale,
                         w_refresh)
from .events import Event, handle_event, hermite_interp
from .loops import solver_loop
from .solvers import SolveResult
from .tableaus import ROS23W, RosenbrockTableau


def _jac_lanes(f, u, p, t, jac=None):
    """Per-lane Jacobian: u (n, B) -> J (n, n, B), lanes last.

    Analytic hook: component-style `jac(u, p, t)` broadcasts over the lane
    axis and returns (n, n, B).  AD fallback: one jvp per state component,
    with the unit tangent e_j in every lane (the lanes never interact), so
    column j is ∂f/∂u_j for all lanes at once.  No lanes-first array and no
    transpose: the layout the fused kernel can hold.  The tangents are
    stacked constant rows: one built from an iota compare makes the TPU
    kernel compiler (Mosaic) abort on the row reads inside f."""
    if jac is not None:
        return jac(u, p, t)
    n = u.shape[0]
    cols = []
    for j in range(n):
        e = jnp.stack([jnp.full(u.shape[1:], float(i == j), u.dtype)
                       for i in range(n)])
        cols.append(jax.jvp(lambda uu: f(uu, p, t), (u,), (e,))[1])
    return jnp.stack(cols, axis=1)


# ---------------------------------------------------------------------------
# lazy-W adapters: build / factor / resolve / masked-select per linsolve mode.
# The factored state is an ordinary pytree of arrays, so it can live in the
# adaptive while_loop carry and be refreshed per lane under a mask — the
# "lazy about its linear algebra" hot path (Jacobian & LU(W) reuse ACROSS
# steps, not just across the s stages of one step).
# ---------------------------------------------------------------------------

def _w_build(J, dt, gam, lanes, dtype):
    """W = I − γ·dt·J, same expressions as the eager step (bitwise-stable)."""
    n = J.shape[0]
    if lanes:
        eye = jnp.eye(n, dtype=dtype)[..., None]
        gdt = (dt * gam)[None, None] if jnp.ndim(dt) else dt * gam
        return eye - gdt * J                               # (n, n, B)
    return jnp.eye(n, dtype=dtype) - dt * gam * J          # (n, n)


def _w_factor(W, mode, lanes):
    """Mode-specific factorization -> carry-able pytree.

    W is (n, n) in scalar mode and (n, n, B) in lanes mode.  "jnp"/scalar:
    LAPACK (lu, piv), batch-first; "lanes": the pivoted lanes-LU kernel body
    (rows/swaps/mults/pivmin lists — a pytree); "pallas": the factorization
    cannot persist across a `pallas_call` boundary, so the carried state is W
    itself, batch-first, and each resolve launches the batched kernel (J
    reuse still saves the expensive jac/jacfwd passes; `nfact` then counts W
    rebuilds)."""
    if not lanes:
        return jax.scipy.linalg.lu_factor(W)
    if mode in ("jnp", None):
        return jax.scipy.linalg.lu_factor(jnp.moveaxis(W, -1, 0))
    if mode == "lanes":
        from repro.kernels.lu.kernel import lu_factor_lanes
        return lu_factor_lanes(W)
    if mode == "pallas":
        return jnp.moveaxis(W, -1, 0)
    raise ValueError(f"unknown linsolve mode {mode!r}")


def _w_resolve(fac, rhs, mode, lanes, lane_tile):
    """Back-substitute one right-hand side against a `_w_factor` state."""
    if not lanes:
        return jax.scipy.linalg.lu_solve(fac, rhs)
    if mode in ("jnp", None):
        return jax.scipy.linalg.lu_solve(fac, rhs.T[..., None])[..., 0].T
    if mode == "lanes":
        from repro.kernels.lu.kernel import lu_resolve_lanes
        return lu_resolve_lanes(fac, rhs)
    if mode == "pallas":
        from repro.kernels.lu.ops import batched_solve
        return batched_solve(fac, rhs.T, lane_tile=lane_tile).T
    raise ValueError(f"unknown linsolve mode {mode!r}")


def _secant_update(J, du, dF, gain, mask, lanes):
    """Extrapolated-secant (Broyden) touch-up of the cached Jacobian.

    J ← J + gain·(ΔF − J·Δu)·Δuᵀ/(Δuᵀ·Δu) on lanes where `mask` holds —
    rank-1, O(n²), no RHS evaluations (ΔF reuses the f(u) values the stage
    loop computes anyway).  gain=2 extrapolates the secant midpoint to the
    endpoint state (exact along Δu for J affine in u — quadratic RHS).
    Skipped where Δu = 0 or the correction is non-finite."""
    if lanes:
        nn = jnp.sum(du * du, axis=0)                      # (B,)
        Jdu = jnp.sum(J * du[None], axis=1)                # (n, B)
        r = dF - Jdu
        corr = (r[:, None] * du[None]
                / jnp.where(nn > 0, nn, 1.0)[None, None])  # (n, n, B)
        ok = (mask & (nn > 0)
              & jnp.all(jnp.isfinite(corr), axis=(0, 1)))[None, None]
    else:
        nn = jnp.sum(du * du)
        corr = (jnp.outer(dF - J @ du, du)
                / jnp.where(nn > 0, nn, 1.0))
        ok = mask & (nn > 0) & jnp.all(jnp.isfinite(corr))
    return jnp.where(ok, J + gain * corr, J)


def _w_select(mask, fac_new, fac_old, mode, lanes):
    """Per-lane masked refresh of the factored state (mask: scalar or (B,))."""
    if not lanes or mode == "lanes":
        # scalar mode: scalar mask; "lanes" leaves are (n, B)/(B,) —
        # trailing-lane axis, so a (B,) mask broadcasts as-is
        sel = lambda a, b: jnp.where(mask, a, b)
    else:
        # "jnp" (lu (B,n,n), piv (B,n)) and "pallas" (W (B,n,n)): leading-B
        sel = lambda a, b: jnp.where(
            mask.reshape(mask.shape + (1,) * (a.ndim - mask.ndim)), a, b)
    return jax.tree_util.tree_map(sel, fac_new, fac_old)


def rosenbrock_nf_per_step(rtab: RosenbrockTableau) -> int:
    """RHS evaluations per step: one per stage, plus f(u1) for Hermite dense
    output unless the tableau ships interpolation weights or its last stage
    argument already IS u1 (ROS23W).  Jacobian/f_t AD passes are not counted
    (same convention as the previous 2-stage engine)."""
    extra = 0 if (rtab.interp_h is not None or rtab.fnew_from_last_stage) else 1
    return rtab.stages + extra


def rosenbrock_step(f, rtab: RosenbrockTableau, u, p, t, dt, *, lanes=False,
                    linsolve="jnp", lane_tile=None, jac=None):
    """One s-stage W-method step.

    Returns (u_new, err, F0, F_new, kds): F_new is f(u_new, t+dt) (reused from
    the last stage when the tableau is stiffly accurate with g_s = u1, or
    None when the tableau interpolates from its own stages); kds are the
    dense-output vectors kd_l = Σ_j interp_h[l, j] U_j (empty tuple if none).
    """
    dtype = u.dtype
    gam = rtab.gamma
    if lanes:
        J = _jac_lanes(f, u, p, t, jac)                 # (n, n, B)
    else:
        J = (jac(u, p, t) if jac is not None
             else jax.jacfwd(lambda uu: f(uu, p, t))(u))  # (n, n)
    # ONE factorization per step, s resolves — the same build/factor/resolve
    # adapters the lazy-W carry uses, so eager and lazy stay one dispatch
    fac = _w_factor(_w_build(J, dt, gam, lanes, dtype), linsolve, lanes)
    return _stage_loop(f, rtab, u, p, t, dt,
                       lambda rhs: _w_resolve(fac, rhs, linsolve, lanes,
                                              lane_tile))


def _stage_loop(f, rtab: RosenbrockTableau, u, p, t, dt, solve, F0=None):
    """The s per-stage solves against an already-factored W (`solve` is a
    rhs -> x closure).  Shared by the eager step above and the lazy-W
    while_loop body (which carries the factorization across steps and passes
    the f(u) it already computed for the secant touch-up as `F0`)."""
    s = rtab.stages
    gam = rtab.gamma
    a, C, d = rtab.a, rtab.C, rtab.d
    dtb = dt if jnp.ndim(dt) == 0 else dt[None]
    Td = jax.jvp(lambda tt: f(u, p, tt), (t,),
                 (jnp.ones_like(t),))[1]                # df/dt
    if F0 is None:
        F0 = f(u, p, t)
    Us = []
    F_last = F0
    for i in range(s):
        if i == 0:
            Fi = F0
        else:
            g = u
            for j in range(i):
                if a[i, j] != 0.0:
                    g = g + a[i, j] * Us[j]
            Fi = f(g, p, t + rtab.c[i] * dt)
        rhs = (gam * dtb) * Fi
        for j in range(i):
            if C[i, j] != 0.0:
                rhs = rhs + (gam * C[i, j]) * Us[j]
        if d[i] != 0.0:
            rhs = rhs + (gam * d[i]) * dtb * dtb * Td
        Us.append(solve(rhs))
        F_last = Fi
    u_new = u
    err = jnp.zeros_like(u)
    for i in range(s):
        if rtab.b[i] != 0.0:
            u_new = u_new + rtab.b[i] * Us[i]
        if rtab.btilde[i] != 0.0:
            err = err + rtab.btilde[i] * Us[i]
    if rtab.interp_h is not None:
        kds = tuple(
            sum((rtab.interp_h[l, j] * Us[j] for j in range(s)
                 if rtab.interp_h[l, j] != 0.0), jnp.zeros_like(u))
            for l in range(rtab.interp_h.shape[0]))
        F_new = None
    else:
        kds = ()
        F_new = (F_last if rtab.fnew_from_last_stage
                 else f(u_new, p, t + dt))
    return u_new, err, F0, F_new, kds


def rosenbrock23_step(f, u, p, t, dt, *, lanes=False, linsolve="jnp",
                      lane_tile=None):
    """Backwards-compatible ROS23 step. Returns (u_new, err, F0, F2)."""
    u_new, err, F0, F_new, _ = rosenbrock_step(
        f, ROS23W, u, p, t, dt, lanes=lanes, linsolve=linsolve,
        lane_tile=lane_tile)
    return u_new, err, F0, F_new


def _dense_eval(rtab, th, u_old, u_cand, F0, F_new, kds, dtb):
    """Dense output at pre-broadcast theta `th` (same rank as the states).

    Stiffly-accurate tableau weights when the tableau ships them:
        u(θ) = (1−θ)·u0 + θ·u1 + θ(1−θ)·(kd1 + θ·kd2 + ...)
    else cubic Hermite on (u0, F0, u1, F_new) — the shared basis from
    `repro.core.events` (lanes=False: th/dtb arrive pre-broadcast)."""
    if rtab.interp_h is not None:
        inner = kds[-1]
        for kd in kds[-2::-1]:
            inner = kd + th * inner
        return (1.0 - th) * u_old + th * u_cand + th * (1.0 - th) * inner
    return hermite_interp(u_old, F0, u_cand, F_new, dtb, th, lanes=False)


def solve_rosenbrock(f, rtab: RosenbrockTableau, u0, p, t0, tf, dt0, *,
                     rtol=1e-6, atol=1e-6, saveat=None, max_iters=100_000,
                     lanes=False, linsolve="jnp", lane_tile=None, jac=None,
                     controller: Optional[PIController] = None,
                     event: Optional[Event] = None, w_reuse=None,
                     batch_axis: Optional[str] = None, bounded_steps=None,
                     checkpoint_every=None):
    """Adaptive s-stage Rosenbrock solve with dense output.

    `jac` is the analytic-Jacobian hook (component-style (u, p, t) -> (n, n)
    resp. (n, n, B)); None falls back to `jacfwd`.  `event` threads the shared
    event machinery (`repro.core.events`) through the stiff family: detection
    + bisection refinement run on the method's dense output (the tableau's
    stiffly-accurate interpolant when it ships one, Hermite cubic otherwise)
    with per-lane termination masks in lanes mode.  When an event is supplied
    the return value is ``(SolveResult, {"event_t", "event_count"})`` — the
    same contract as `solve_adaptive`.

    `w_reuse` makes the step loop lazy about its linear algebra: the current
    Jacobian, the factored LU(W) and the dt it was factored at ride in the
    while_loop carry, and J is only re-evaluated / W only re-factored when
    the `WReusePolicy` freshness controller asks (see
    `repro.core.controller`).  ``None``/``False`` keeps today's eager
    every-step behaviour bitwise (the carry does not even contain the lazy
    state); ``True`` enables the default policy; a `WReusePolicy` instance
    customizes the thresholds.  `SolveResult.njac`/`nfact` report the work
    either way (eager: both equal naccept + nreject).

    The refresh runs under an any()-gated `lax.cond`, so the counter savings
    are real wall time on every path.  On the lanes paths (array / kernel)
    `jnp.any` already reduces over the batch.  Under `vmap` a plain
    `jnp.any` predicate is per-trajectory — BATCHED — and vmap lowers a
    batched cond to a select that executes BOTH branches every step; callers
    that vmap this solver must bind an axis name
    (``jax.vmap(one, axis_name=ax)``) and pass it as ``batch_axis=ax``: the
    predicates are then `psum`-reduced over the vmap axis, which yields an
    UNBATCHED boolean, keeps the cond a genuine branch, and makes the
    refresh genuinely skippable (jacfwd + O(n³) elimination not executed)
    whenever no trajectory in the batch asked for it.
    `repro.core.ensemble.solve_ensemble_local` wires this automatically for
    ``ensemble="vmap"``.

    ``bounded_steps``/``checkpoint_every`` select the reverse-differentiable
    bounded loop (`repro.core.loops.solver_loop`) with the frozen-step
    discrete adjoint: the controller/freshness chain is severed from the
    autodiff graph and the differentiated stage solves re-run at
    ``where(accept, dt, 0)``, so the reverse pass only transposes accepted
    steps.  Same step sequence as the while path whenever the bound covers
    the true iteration count (too small => ``status == 1``).
    """
    policy = (None if (w_reuse is None or w_reuse is False)
              else (w_reuse if isinstance(w_reuse, WReusePolicy)
                    else WReusePolicy()))
    dtype = u0.dtype
    q = min(rtab.order, rtab.embedded_order)  # order the estimator measures
    ctrl = controller or PIController.for_order(q)
    nf_step = rosenbrock_nf_per_step(rtab)
    cshape = (u0.shape[-1],) if lanes else ()
    axes = 0 if lanes else None
    t0 = jnp.asarray(t0, dtype)
    tf = jnp.asarray(tf, dtype)
    if saveat is None:
        saveat = jnp.asarray([tf], dtype)
    saveat = jnp.asarray(saveat, dtype)
    S = saveat.shape[0]
    us0 = jnp.zeros((S,) + u0.shape, dtype)
    pre = (saveat[:, None] <= t0).reshape((S,) + (1,) * u0.ndim)  # see solvers
    us0 = jnp.where(pre, u0[None], us0)

    gam = rtab.gamma

    def jac_eval(u, t):
        if lanes:
            return _jac_lanes(f, u, p, t, jac)
        return (jac(u, p, t) if jac is not None
                else jax.jacfwd(lambda uu: f(uu, p, t))(u))

    def any_lane(x):
        # cond predicate that is UNIFORM over the whole ensemble batch.
        # In lanes mode jnp.any already reduces over the (B,) lane axis;
        # under vmap it is a per-trajectory (batched) bool, and a batched
        # cond lowers to a select executing both branches — psum over the
        # caller-bound vmap axis returns an unbatched scalar, keeping the
        # refresh cond a real branch (see the docstring).
        a = jnp.any(x)
        if batch_axis is not None:
            a = jax.lax.psum(a.astype(jnp.int32), batch_axis) > 0
        return a

    carry0 = dict(
        t=jnp.broadcast_to(t0, cshape), u=u0,
        dt=jnp.broadcast_to(jnp.asarray(dt0, dtype), cshape),
        enorm_prev=jnp.ones(cshape, dtype),
        done=jnp.zeros(cshape, bool), us=us0,
        naccept=jnp.zeros(cshape, jnp.int32),
        nreject=jnp.zeros(cshape, jnp.int32),
        status=jnp.zeros(cshape, jnp.int32),
        iters=jnp.asarray(0, jnp.int32),
        event_t=jnp.full(cshape, jnp.inf, dtype),
        event_count=jnp.zeros(cshape, jnp.int32))
    if policy is not None:
        # lazy-W state: everything the freshness controller needs to decide,
        # per lane, whether this step may ride on last step's linear algebra
        J0 = jac_eval(u0, carry0["t"])
        fac0 = _w_factor(_w_build(J0, carry0["dt"], gam, lanes, dtype),
                         linsolve, lanes)
        carry0.update(
            J=J0, fac=fac0, dt_fact=carry0["dt"],
            age=jnp.zeros(cshape, jnp.int32),
            jac_stale=jnp.zeros(cshape, bool),
            u_prev=u0, F_prev=jnp.zeros_like(u0),
            was_accept=jnp.zeros(cshape, bool),
            njac=jnp.ones(cshape, jnp.int32),
            nfact=jnp.ones(cshape, jnp.int32))

    def _bc(v):
        return v if jnp.ndim(v) == 0 else v[None]

    def cond(c):
        return (c["iters"] < max_iters) & jnp.any(~c["done"])

    bounded = bounded_steps is not None

    def body(c):
        t, u, dt = c["t"], c["u"], c["dt"]
        active = ~c["done"]
        # done lanes step at dt = 0 — an exact no-op of the stage solves
        # (output-invariant either way, but nonzero dt lets finished lanes
        # synthesize garbage that would poison the reverse pass via 0 * inf)
        dt_step = jnp.where(active, jnp.minimum(dt, tf - t),
                            jnp.asarray(0.0, dtype))
        if policy is None:
            u_cand, err, F0, F_new, kds = rosenbrock_step(
                f, rtab, u, p, t, dt_step, lanes=lanes, linsolve=linsolve,
                lane_tile=lane_tile, jac=jac)
        else:
            need_jac, drift_fact = w_refresh(policy, gam, dt_step,
                                             c["dt_fact"], c["jac_stale"])
            need_jac = need_jac & active
            F0 = f(u, p, t)
            if policy.secant:
                # keep the cached J alive: extrapolated-secant touch-up from
                # the accepted step's own states/RHS values (rank-1, O(n²))
                upd = c["was_accept"] & ~need_jac & active
                J_base = _secant_update(c["J"], u - c["u_prev"],
                                        F0 - c["F_prev"], policy.secant,
                                        upd, lanes)
            else:
                upd = jnp.zeros(cshape, bool)
                J_base = c["J"]
            need_fact = (drift_fact | upd) & active
            # without secant updates, dt freezes AT dt_fact between
            # refreshes (the LSODA/BDF amortization pattern): the factored W
            # is reused VERBATIM and the PI proposal takes effect —
            # quantized — once it drifts out of the γ-scaled band
            dt_step = jnp.where(
                need_fact, dt_step,
                jnp.where(active, jnp.minimum(c["dt_fact"], tf - t),
                          jnp.asarray(0.0, dtype)))

            def refresh(state):
                J_old, fac_old, dtf_old = state
                J_new = jax.lax.cond(any_lane(need_jac),
                                     lambda: jac_eval(u, t), lambda: J_old)
                jmask = (need_jac[None, None] if lanes else need_jac)
                J_sel = jnp.where(jmask, J_new, J_old)
                fac_new = _w_factor(_w_build(J_sel, dt_step, gam, lanes,
                                             dtype), linsolve, lanes)
                fac_sel = _w_select(need_fact, fac_new, fac_old,
                                    linsolve, lanes)
                return (J_sel, fac_sel,
                        jnp.where(need_fact, dt_step, dtf_old))

            J, fac, dt_fact = jax.lax.cond(
                any_lane(need_fact), refresh, lambda s: s,
                (J_base, c["fac"], c["dt_fact"]))
            u_cand, err, _, F_new, kds = _stage_loop(
                f, rtab, u, p, t, dt_step,
                lambda rhs: _w_resolve(fac, rhs, linsolve, lanes, lane_tile),
                F0=F0)
        enorm = hairer_norm(err, u, u_cand, atol, rtol, axes=axes)
        if bounded:
            # Frozen-step discrete adjoint: the controller/freshness chain is
            # severed from the autodiff graph — we differentiate the realized
            # step sequence, not the step-size policy.
            enorm = jax.lax.stop_gradient(enorm)
        finite = jnp.isfinite(u_cand)
        finite = jnp.all(finite, axis=0) if lanes else jnp.all(finite)
        accept = (enorm <= 1.0) & finite & active
        dt_next, enorm_prev = pi_propose(ctrl, dt, enorm, c["enorm_prev"],
                                         accept)
        if policy is not None and not policy.secant:
            # frozen-J rejection: refresh and retry at the SAME dt before
            # blaming (and slashing) the step size.  With secant updates the
            # cached J already tracks the state, so a rejection is a genuine
            # dt problem and the PI shrink stands.
            dt_next = w_dt_blame(accept, need_jac, dt_step, dt_next)
        dt_try = dt_step   # pre-adjoint-mask attempt size (dtmin-floor check)
        if bounded:
            # Adjoint-safe second pass (same pattern as solvers.solve_adaptive):
            # the cascade above was a primal-only probe; re-run the stage
            # solves at where(accept, dt, 0) — an exact no-op on rejected
            # attempts — so the reverse pass never transposes a stage solve
            # at an off-trajectory (possibly overflowed) rejected candidate.
            dt_step = jnp.where(accept, dt_step, jnp.asarray(0.0, dtype))
            if policy is None:
                u_cand, err, F0, F_new, kds = rosenbrock_step(
                    f, rtab, u, p, t, dt_step, lanes=lanes, linsolve=linsolve,
                    lane_tile=lane_tile, jac=jac)
            else:
                u_cand, err, _, F_new, kds = _stage_loop(
                    f, rtab, u, p, t, dt_step,
                    lambda rhs: _w_resolve(fac, rhs, linsolve, lanes,
                                           lane_tile),
                    F0=F0)
        t_new = jnp.where(accept, t + dt_step, t)

        # ---- events: shared machinery on the method's dense output ---------
        if event is not None:
            def interp_fn(theta):
                th = theta[None] if lanes else theta
                return _dense_eval(rtab, th, u, u_cand, F0, F_new, kds,
                                   dt_step if jnp.ndim(dt_step) == 0
                                   else dt_step[None])

            u_next, t_new, ev_t, ev_n, term = handle_event(
                event, interp_fn, u, u_cand, p, t, dt_step, t_new, accept,
                c["event_t"], c["event_count"], lanes=lanes)
        else:
            u_next = u_cand
            ev_t, ev_n = c["event_t"], c["event_count"]
            term = jnp.zeros(cshape, bool)

        u_new = jnp.where(_bc(accept), u_next, u)

        # dense-output grid save
        eps = 1e-7 * jnp.maximum(jnp.abs(t_new), 1.0)
        if lanes:
            crossed = ((saveat[:, None] > t[None]) &
                       (saveat[:, None] <= t_new[None] + eps[None]) &
                       accept[None])
            theta = jnp.clip((saveat[:, None] - t[None])
                             / jnp.where(dt_step[None] == 0, 1.0,
                                         dt_step[None]), 0.0, 1.0)
            th = theta[:, None, :]
            dtb = dt_step[None, None, :]
            mask = crossed[:, None, :]
        else:
            crossed = (saveat > t) & (saveat <= t_new + eps) & accept
            theta = jnp.clip((saveat - t)
                             / jnp.where(dt_step == 0, 1.0, dt_step),
                             0.0, 1.0)
            sh = (S,) + (1,) * u.ndim
            th = theta.reshape(sh)
            dtb = dt_step
            mask = crossed.reshape(sh)
        vals = _dense_eval(rtab, th, u[None], u_cand[None],
                           None if F0 is None else F0[None],
                           None if F_new is None else F_new[None],
                           tuple(kd[None] for kd in kds), dtb)
        us = jnp.where(mask, vals, c["us"])

        # dt pinned at the controller floor and still rejecting: the retry is
        # bit-identical, so the lane can never recover — terminate with a
        # distinct status instead of spinning silently to max_iters.  On the
        # lazy path a rejection taken on a REUSED J is exempt: the next
        # attempt refreshes J (w_mark_stale / w_dt_blame), so its retry is
        # NOT identical and may well accept at the same dt.
        hopeless = active & ~accept & ~(dt_try > ctrl.dtmin)
        if policy is not None:
            hopeless = hopeless & need_jac
        statusv = jnp.where(hopeless,
                            jnp.asarray(STATUS_DTMIN_EXHAUSTED, jnp.int32),
                            c["status"])
        done = (c["done"] | term | hopeless
                | (t_new >= tf - 1e-7 * jnp.maximum(jnp.abs(tf), 1.0)))
        out = dict(t=t_new, u=u_new, dt=dt_next, enorm_prev=enorm_prev,
                   done=done, us=us,
                   naccept=c["naccept"] + accept.astype(jnp.int32),
                   nreject=c["nreject"] + (active & ~accept).astype(jnp.int32),
                   status=statusv, iters=c["iters"] + 1,
                   event_t=ev_t, event_count=ev_n)
        if policy is not None:
            fresh = need_jac
            age = jnp.where(need_jac, 0, c["age"]) + accept.astype(jnp.int32)
            out.update(
                J=J, fac=fac, dt_fact=dt_fact, age=age,
                jac_stale=w_mark_stale(policy, accept, enorm,
                                       c["enorm_prev"], age, fresh),
                u_prev=jnp.where(_bc(accept), u, c["u_prev"]),
                F_prev=jnp.where(_bc(accept), F0, c["F_prev"]),
                was_accept=accept,
                njac=c["njac"] + need_jac.astype(jnp.int32),
                nfact=c["nfact"] + need_fact.astype(jnp.int32))
        return out

    out = solver_loop(cond, body, carry0, bounded_steps=bounded_steps,
                      checkpoint_every=checkpoint_every)
    nsteps = out["naccept"] + out["nreject"]
    res = SolveResult(
        ts=saveat, us=out["us"], t_final=out["t"], u_final=out["u"],
        naccept=out["naccept"], nreject=out["nreject"],
        status=jnp.where(out["status"] > 0, out["status"],
                         jnp.where(out["done"], 0, 1)).astype(jnp.int32),
        nf=nsteps * nf_step,
        njac=out["njac"] if policy is not None else nsteps,
        nfact=out["nfact"] if policy is not None else nsteps,
        iters=out["iters"])
    if event is not None:
        return res, dict(event_t=out["event_t"], event_count=out["event_count"])
    return res


def solve_rosenbrock23(f, u0, p, t0, tf, dt0, *, rtol=1e-6, atol=1e-6,
                       saveat=None, max_iters=100_000, lanes=False,
                       linsolve="jnp", lane_tile=None,
                       controller: Optional[PIController] = None,
                       event: Optional[Event] = None):
    """Rosenbrock23 through the generic engine (backwards-compatible entry)."""
    return solve_rosenbrock(f, ROS23W, u0, p, t0, tf, dt0, rtol=rtol,
                            atol=atol, saveat=saveat, max_iters=max_iters,
                            lanes=lanes, linsolve=linsolve,
                            lane_tile=lane_tile, controller=controller,
                            event=event)
