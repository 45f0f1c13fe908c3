"""SDE steppers (paper §3.2, §5.2.2, §6.8): fixed-dt, kernel-shaped.

Methods (matching the paper's GPU kernel set):
  em         — GPUEM: Euler-Maruyama, Ito; diagonal AND general (n×m) noise.
  platen_w2  — GPUSIEA role: explicit weak-order-2 Platen scheme
               (Kloeden & Platen §14.2), diagonal noise only — the weak-order-2
               stochastic generalization of the midpoint/improved-Euler family.
  heun_strat — Stratonovich Heun (extra, beyond paper).

Noise is counter-based: dW for step k is drawn from fold_in(key, k), so the
stepper needs no noise storage (the paper's per-thread PRNG state), trajectories
are independent across lanes, and any step's noise can be replayed (used by the
pathwise tests and by the pallas/XLA cross-validation).

All steppers are shape-polymorphic like the ODE engine: u (n,) scalar-mode or
(n, B) lanes-mode; the SAME definition runs vmapped, lane-fused, and inside the
Pallas EM kernel (kernels/em).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .controller import (STATUS_DTMIN_EXHAUSTED, PIController, hairer_norm,
                         pi_propose)
from .events import Event, handle_event, linear_interp
from .loops import solver_loop
from .problem import EnsembleProblem, SDEProblem
from .solvers import SolveResult

Array = Any


def _sqrt_dt(dt, dtype):
    return jnp.sqrt(jnp.asarray(dt, dtype))


def apply_noise(g_val, dW, noise: str):
    """g(u)·dW with g_val (n,[B]) diagonal or (n,m,[B]) general; dW (m,[B])."""
    if noise == "diagonal":
        return g_val * dW
    # general: contract the noise axis (axis 1 of g_val)
    return jnp.einsum("nm...,m...->n...", g_val, dW)


def em_step(f, g, u, p, t, dt, dW, noise="diagonal"):
    """X' = X + f dt + g dW  (Ito; strong 0.5 / weak 1)."""
    return u + f(u, p, t) * dt + apply_noise(g(u, p, t), dW, noise)


def heun_strat_step(f, g, u, p, t, dt, dW, noise="diagonal"):
    """Stratonovich Heun (strong 0.5 / weak 1 in Stratonovich sense)."""
    du1 = f(u, p, t) * dt + apply_noise(g(u, p, t), dW, noise)
    ub = u + du1
    du2 = f(ub, p, t + dt) * dt + apply_noise(g(ub, p, t + dt), dW, noise)
    return u + 0.5 * (du1 + du2)


def platen_w2_step(f, g, u, p, t, dt, dW, noise="diagonal"):
    """Explicit weak-order-2 Platen scheme, diagonal noise (Kloeden & Platen
    (15.1.1)/(14.2.4) family). Supporting values:
        ubar = u + a dt + b dW ;  u± = u + a dt ± b sqrt(dt)
        u'   = u + dt/2 (a(ubar)+a(u))
                 + dW/4 (b(u+)+b(u-)+2 b(u))
                 + (dW^2-dt)/(4 sqrt(dt)) (b(u+)-b(u-))
    """
    if noise != "diagonal":
        raise ValueError("platen_w2 supports diagonal noise only (as the "
                         "paper's GPUSIEA)")
    a0 = f(u, p, t)
    b0 = g(u, p, t)
    sdt = _sqrt_dt(dt, u.dtype)
    drift = u + a0 * dt
    ubar = drift + b0 * dW
    up = drift + b0 * sdt
    um = drift - b0 * sdt
    t1 = t + dt
    a1 = f(ubar, p, t1)
    bp = g(up, p, t1)
    bm = g(um, p, t1)
    return (u + 0.5 * dt * (a1 + a0)
            + 0.25 * dW * (bp + bm + 2.0 * b0)
            + 0.25 * (dW * dW - dt) / sdt * (bp - bm))


def milstein_step(f, g, u, p, t, dt, dW, noise="diagonal"):
    """Milstein (diagonal noise): strong order 1.0 — beyond the paper's kernel
    set (GPUEM is strong 0.5). The derivative term comes from forward-mode AD
    on the user's diffusion (automated translation again: no hand Jacobians).
        X' = X + a dt + b dW + 1/2 ((∂b/∂x)·b) (dW² - dt)
    Exact for componentwise diffusions g_i(u_i) (GBM, CLE birth/death terms);
    cross-component ∂g_i/∂u_j would need Lévy-area terms (not included).
    """
    if noise != "diagonal":
        raise ValueError("milstein currently supports diagonal noise")
    a0 = f(u, p, t)
    b0, db = jax.jvp(lambda uu: g(uu, p, t), (u,), (g(u, p, t),))
    # db = (∂b/∂u)·b elementwise along the diagonal-noise structure
    return u + a0 * dt + b0 * dW + 0.5 * db * (dW * dW - dt)


SDE_STEPPERS = {
    "em": em_step,
    "heun_strat": heun_strat_step,
    "platen_w2": platen_w2_step,
    "siea": platen_w2_step,  # paper-facing alias
    "milstein": milstein_step,
}


# ----------------------------------------------------------------------------
# embedded error pairs (RSwM-style rejection sampling, no step doubling)
# ----------------------------------------------------------------------------
#
# An embedded pair returns (u_prop, err) from ONE pass over the interval: the
# propagated solution plus a local error estimate built from a cheap companion
# scheme on the SAME Brownian increment.  Against step doubling (three stepper
# evaluations + an extra Brownian-tree descent per attempted step) this costs
# ~1 stepper evaluation and ONE descent — the ~2x adaptive-SDE win recorded in
# ROADMAP.md.  Rejection stays exact for free: increments come from the
# virtual Brownian tree, a pure function of (seed; lane, row, dyadic time),
# so a rejected step retried with a smaller dt replays the path bitwise.

def em_embedded_step(f, g, u, p, t, dt, dW, noise="diagonal"):
    """Euler-Maruyama propagation + embedded tamed-Milstein-difference error.

    The companion is the drift-tamed (Hutzenthaler-Jentzen) diagonal Milstein
    scheme; since it shares the drift and diffusion increments with EM, the
    pair difference is the Milstein correction plus the drift-taming term:

        err = 1/2 ((∂b/∂x)·b) (dW² - dt)  +  (a - a/(1 + dt|a|)) dt

    The first term — O(dt) in the strong sense — is the leading term EM omits
    relative to strong order 1 and dominates for genuinely stochastic steps;
    the second, O(dt²), keeps the estimator drift-aware so the controller
    still resolves the deterministic dynamics when the diffusion is locally
    negligible (a pure Milstein difference is blind there).  Diagonal noise
    only (a general-noise companion would need Lévy areas — use
    ``error_est="doubling"`` there).

    The pair deliberately propagates the PLAIN EM solution, not the
    Milstein-corrected one: acceptance conditions on |dW² - dt|, and adding
    the correction only on accepted steps would accumulate the truncated
    tail of the chi-square as a systematic bias (the classic hazard of
    noise-adapted step sizes).  EM's own missing term telescopes against the
    true path regardless of the acceptance rule.
    """
    if noise != "diagonal":
        raise ValueError("em embedded pair supports diagonal noise only; "
                         "use error_est='doubling' for general noise")
    a0 = f(u, p, t)
    b0, db = jax.jvp(lambda uu: g(uu, p, t), (u,), (g(u, p, t),))
    err = (0.5 * db * (dW * dW - dt)
           + (a0 - a0 / (1.0 + dt * jnp.abs(a0))) * dt)
    return u + a0 * dt + b0 * dW, err


def milstein_embedded_step(f, g, u, p, t, dt, dW, noise="diagonal"):
    """Milstein propagation + deterministic embedded companion error.

    Two estimator terms, both deterministic in dW (so acceptance never
    conditions on the realized increments — no truncation-bias floor, unlike
    the em pair):

    * drift: the increment-tamed companion (Hutzenthaler & Jentzen taming,
      a -> a / (1 + dt|a|)), whose difference
      ``(a - a/(1 + dt|a|)) dt = a|a| dt²/(1+dt|a|)`` (O(dt²)) tracks the
      deterministic-Taylor remainder and drift-explosion regimes;
    * diffusion: the rms of the leading neglected Ito-Taylor term
      L¹L¹b · I₍₁,₁,₁₎ — ``|∂((∂b)·b)·b| · dt^1.5 / sqrt(6)`` (E[I₁₁₁²] =
      dt³/6) via a nested diffusion JVP.  Without it the estimator is blind
      on diffusion-dominated problems (zero-drift SDEs would accept any dt).

    Extra cost over the plain stepper: diffusion JVPs only — no drift
    evaluations, so nf_per_attempt stays 1.
    """
    if noise != "diagonal":
        raise ValueError("milstein currently supports diagonal noise")
    a0 = f(u, p, t)

    def db_of(uu):
        bb = g(uu, p, t)
        return jax.jvp(lambda w: g(w, p, t), (uu,), (bb,))[1]

    b0 = g(u, p, t)
    db, ddb = jax.jvp(db_of, (u,), (b0,))      # (∂b)·b and ∂((∂b)·b)·b
    u_new = u + a0 * dt + b0 * dW + 0.5 * db * (dW * dW - dt)
    dt15 = dt * _sqrt_dt(dt, u.dtype)
    err = ((a0 - a0 / (1.0 + dt * jnp.abs(a0))) * dt
           + jnp.abs(ddb) * dt15 / jnp.sqrt(jnp.asarray(6.0, u.dtype)))
    return u_new, err


class EmbeddedPair(NamedTuple):
    """An SDE embedded error pair as registered on a `MethodSpec`.

    fn:             (f, g, u, p, t, dt, dW, noise) -> (u_prop, err)
    est_order:      dt-order of the estimator (PI controller exponents)
    nf_per_attempt: drift evaluations charged to `nf` per attempted step
    """
    fn: Callable
    est_order: int
    nf_per_attempt: int


# name -> EmbeddedPair.  Steppers absent here support error_est="doubling"
# only (the registry derives the capability tuple from this).
SDE_EMBEDDED = {
    "em": EmbeddedPair(em_embedded_step, est_order=1, nf_per_attempt=1),
    # estimator leading term is O(dt^1.5) (the L¹L¹b proxy); est_order=1 is
    # the conservative integer controller exponent for it
    "milstein": EmbeddedPair(milstein_embedded_step, est_order=1,
                             nf_per_attempt=1),
}


def counter_normals(key, step, shape, dtype):
    """Counter-based N(0,1) draw for a given step index (replayable)."""
    return jax.random.normal(jax.random.fold_in(key, step), shape, dtype)


def sde_nf_per_step(method: str) -> int:
    """Drift evaluations per step (the nf work proxy), per method.

    em and milstein evaluate the drift once (milstein's extra work is a
    diffusion JVP, not an RHS call); the two-stage schemes evaluate it twice.
    """
    return 1 if method in ("em", "milstein") else 2


def sde_save_grid(t0, dt, n_steps: int, save_every: int, dtype):
    """The fixed-step snapshot times: t0 + dt*save_every*(1..S)."""
    return jnp.asarray(t0, dtype) + jnp.asarray(dt, dtype) * save_every \
        * jnp.arange(1, n_steps // save_every + 1, dtype=dtype)


def _sde_snapshot(us, u, k, save_every: int, select: bool = False):
    """Masked snapshot write for step k (shared by the fixed-dt loop bodies).

    ``select=True`` writes through an iota mask over the save axis instead of
    a dynamic_update_slice, which the TPU kernel compiler (Mosaic) does not
    lower; the Pallas body uses it.  Same values, O(S) work per snapshot."""
    s = (k + 1) // save_every - 1

    def write(us):
        if select:
            hit = jax.lax.broadcasted_iota(jnp.int32, us.shape, 0) == s
            return jnp.where(hit, u[None], us)
        return jax.lax.dynamic_update_slice(us, u[None],
                                            (s,) + (0,) * (us.ndim - 1))

    return jax.lax.cond((k + 1) % save_every == 0, write, lambda us: us, us)


def sde_step_and_save(stepper, f, g, noise: str, u, us, p, t0, dt, k, z,
                      save_every: int, select: bool = False):
    """ONE fixed-dt step + masked snapshot write — the loop body every SDE
    execution path shares (vmap, XLA lanes, Pallas kernel), so the
    (step, save-index) plumbing that bitwise cross-backend parity depends on
    exists exactly once.  Layout-polymorphic: u (n,)/(n, B) with us
    (S, n)/(S, n, B); z is the N(0,1) draw for step k.  ``select``: see
    `_sde_snapshot`."""
    dtv = jnp.asarray(dt, u.dtype)
    t = t0 + k * dtv
    u = stepper(f, g, u, p, t, dtv, z * jnp.sqrt(dtv), noise)
    us = _sde_snapshot(us, u, k, save_every, select)
    return u, us


def sde_event_state0(cshape, t0, dtype):
    """Initial per-control-element event/termination state for the fixed-dt
    event-aware loop body: (done, t_out, naccept, event_t, event_count)."""
    return dict(done=jnp.zeros(cshape, bool),
                t_out=jnp.broadcast_to(jnp.asarray(t0, dtype), cshape),
                naccept=jnp.zeros(cshape, jnp.int32),
                event_t=jnp.full(cshape, jnp.inf, dtype),
                event_count=jnp.zeros(cshape, jnp.int32))


def sde_step_save_event(stepper, f, g, noise: str, ev: Event, u, us, estate,
                        p, t0, dt, k, z, save_every: int,
                        select: bool = False):
    """Event-aware variant of `sde_step_and_save` — the shared fixed-dt loop
    body with per-lane termination (paper §6.6 on the SDE family).

    Event times are located by bisection on the piecewise-linear path output
    (`repro.core.events`).  Terminal hits freeze the element's state/lane; a
    non-terminal affect is applied at the event point and integration resumes
    at the step's grid end (the fixed grid is never rewound).  estate is the
    dict from `sde_event_state0`.  Layout-polymorphic like the no-event body,
    so the vmap / XLA-lanes / Pallas paths stay bitwise-identical.
    """
    dtv = jnp.asarray(dt, u.dtype)
    t = t0 + k * dtv
    lanes = u.ndim == 2
    active = ~estate["done"]
    u_new = stepper(f, g, u, p, t, dtv, z * jnp.sqrt(dtv), noise)

    def interp_fn(theta):
        return linear_interp(u, u_new, theta, lanes=lanes)

    u_next, t_next, ev_t, ev_n, term = handle_event(
        ev, interp_fn, u, u_new, p, t, dtv, t + dtv, active,
        estate["event_t"], estate["event_count"], lanes=lanes)
    act_e = active[None] if lanes else active
    u = jnp.where(act_e, u_next, u)
    # terminal: report the located event time; otherwise the grid time
    t_out = jnp.where(term, t_next, jnp.where(active, t + dtv,
                                              estate["t_out"]))
    us = _sde_snapshot(us, u, k, save_every, select)
    estate = dict(done=estate["done"] | term, t_out=t_out,
                  naccept=estate["naccept"] + active.astype(jnp.int32),
                  event_t=ev_t, event_count=ev_n)
    return u, us, estate


def sde_resume_init(u0, p, t0, dt, n_steps, lane):
    """Fresh per-lane resume carry for the fixed-dt SDE loop (lanes mode).

    u0 (n, B); p (k, B); t0/dt scalars or (B,); n_steps scalar or (B,) int32
    per-lane step counts; lane scalar or (B,) uint32 GLOBAL lane indices —
    the counter-RNG stream key.  The stream key travels WITH the carry, so a
    recycled slot keeps its request's noise stream: `sde_resume_body` draws
    step k of lane g from counter_normals_threefry(seed, k, g, row) exactly
    like `repro.kernels.em.ref.ref_solve` does, making slot recycling
    bitwise-invisible.
    """
    dtype = u0.dtype
    cshape = (u0.shape[-1],)
    tv = jnp.broadcast_to(jnp.asarray(t0, dtype), cshape).astype(dtype)
    dtv = jnp.broadcast_to(jnp.asarray(dt, dtype), cshape).astype(dtype)
    return dict(
        u=u0, p=p,
        k=jnp.zeros(cshape, jnp.int32),
        n_steps=jnp.broadcast_to(jnp.asarray(n_steps, jnp.int32), cshape),
        t0=tv, dt=dtv,
        lane=jnp.broadcast_to(jnp.asarray(lane, jnp.uint32), cshape),
        done=jnp.zeros(cshape, bool),
        t_out=tv,
        naccept=jnp.zeros(cshape, jnp.int32),
        nf=jnp.zeros(cshape, jnp.int32),
        status=jnp.zeros(cshape, jnp.int32),
        event_t=jnp.full(cshape, jnp.inf, dtype),
        event_count=jnp.zeros(cshape, jnp.int32),
        iters=jnp.asarray(0, jnp.int32),
    )


def sde_resume_body(f, g, method: str, noise: str, m_noise: int, seed,
                    event: Optional[Event] = None):
    """Per-lane resumable fixed-dt SDE step body over the carry from
    `sde_resume_init` — the ops of `sde_step_and_save` (or
    `sde_step_save_event`) with per-lane (k, t0, dt, n_steps, lane) instead
    of shared scalars, and no snapshot buffer (serving returns final states).
    Done lanes are write-masked, so mixed-progress slots are exact no-ops;
    active lanes realize elementwise the SAME expressions as the fresh loop
    body on the same (seed; step, lane, row) counters — bitwise recycling.
    """
    stepper = SDE_STEPPERS[method]
    nfps = sde_nf_per_step(method)

    def body(c):
        from repro.kernels.rng import counter_normals_threefry
        u, p = c["u"], c["p"]
        dtype = u.dtype
        B = u.shape[-1]
        active = ~c["done"]
        k, dtv = c["k"], c["dt"]
        t = c["t0"] + k * dtv
        lane = jnp.broadcast_to(c["lane"][None, :], (m_noise, B))
        rows = jax.lax.broadcasted_iota(jnp.uint32, (m_noise, B), 0)
        z = counter_normals_threefry(seed, k, lane, rows, dtype)
        u_new = stepper(f, g, u, p, t, dtv, z * jnp.sqrt(dtv), noise)
        if event is not None:
            def interp_fn(theta):
                return linear_interp(u, u_new, theta, lanes=True)

            u_next, t_next, ev_t, ev_n, term = handle_event(
                event, interp_fn, u, u_new, p, t, dtv, t + dtv, active,
                c["event_t"], c["event_count"], lanes=True)
        else:
            u_next = u_new
            t_next = t + dtv
            ev_t, ev_n = c["event_t"], c["event_count"]
            term = jnp.zeros((B,), bool)
        u_out = jnp.where(active[None], u_next, u)
        t_out = jnp.where(term, t_next,
                          jnp.where(active, t + dtv, c["t_out"]))
        k_new = k + active.astype(jnp.int32)
        done = c["done"] | term | (k_new >= c["n_steps"])
        return dict(
            u=u_out, p=p, k=k_new, n_steps=c["n_steps"], t0=c["t0"], dt=dtv,
            lane=c["lane"], done=done, t_out=t_out,
            naccept=c["naccept"] + active.astype(jnp.int32),
            nf=c["nf"] + active.astype(jnp.int32) * nfps,
            status=c["status"], event_t=ev_t, event_count=ev_n,
            iters=c["iters"] + 1,
        )

    return body


def sde_solve_fixed(prob: SDEProblem, u0, p, t0, dt, n_steps: int, key,
                    method: str = "em", save_every: int = 1,
                    noise_table: Optional[Array] = None,
                    remat: bool = False) -> SolveResult:
    """Fixed-dt SDE integration as scan(fori(step)); kernel-shaped state flow.

    u0: (n,) or (n, B) lanes. Noise per step: (m,) / (m, B).
    noise_table: optional (n_steps, m[, B]) pre-drawn N(0,1) (pathwise tests).
    remat=True checkpoints each save segment for reverse-mode AD: bitwise
    the same primal, the backward pass replays the counter-RNG increments
    from the segment-boundary carry instead of storing every step
    (O(S + save_every) adjoint memory; pathwise replay is exact because the
    noise is a pure function of the step index).
    """
    assert n_steps % save_every == 0
    S = n_steps // save_every
    stepper = SDE_STEPPERS[method]
    dtype = u0.dtype
    dt = jnp.asarray(dt, dtype)
    sdt = _sqrt_dt(dt, dtype)
    m = prob.noise_dim()
    nshape = (m,) + u0.shape[1:]

    def one(k, uk):
        u, t = uk
        if noise_table is not None:
            z = noise_table[k].astype(dtype)
        else:
            z = counter_normals(key, k, nshape, dtype)
        u = stepper(prob.f, prob.g, u, p, t, dt, z * sdt, prob.noise)
        return (u, t + dt)

    def inner(carry, s):
        u, t = carry
        k0 = s * save_every

        def body(i, uk):
            return one(k0 + i, uk)

        u, t = jax.lax.fori_loop(0, save_every, body, (u, t))
        return (u, t), u

    if remat:
        inner = jax.checkpoint(inner)
    (u_f, t_f), us = jax.lax.scan(inner, (u0, jnp.asarray(t0, dtype)),
                                  jnp.arange(S))
    ts = jnp.asarray(t0, dtype) + dt * save_every * jnp.arange(1, S + 1,
                                                               dtype=dtype)
    return SolveResult(ts=ts, us=us, t_final=t_f, u_final=u_f,
                       naccept=jnp.asarray(n_steps), nreject=jnp.asarray(0),
                       status=jnp.asarray(0),
                       nf=jnp.asarray(n_steps * (2 if method != "em" else 1)),
                       iters=jnp.asarray(n_steps, jnp.int32))


# ----------------------------------------------------------------------------
# adaptive driver (while_loop): embedded-pair or step-doubling error + virtual
# Brownian tree (RSwM-style rejection-safe noise), scalar/lanes polymorphic
# ----------------------------------------------------------------------------

def default_bridge_depth(t0, tf, dt0, min_depth: int = 6,
                         max_depth: int = 22) -> int:
    """Dyadic resolution of the virtual Brownian tree for adaptive stepping.

    Depth D puts the finest grid at (tf-t0)/2**D; the controller can shrink
    steps to 2 grid cells, so the default gives ~64x refinement headroom below
    dt0 (steps at the floor force-accept — raise the depth for very tight
    tolerances).  Static (python) arithmetic: the depth is part of the
    compiled program, identical on every strategy/backend.
    """
    import math
    n0 = max(1.0, (float(tf) - float(t0)) / float(dt0))
    return int(min(max_depth, max(min_depth, math.ceil(math.log2(n0)) + 6)))


def sde_solve_adaptive(f, g, stepper, noise: str, u0, p, t0, tf, dt0, *,
                       seed, lane_idx, m_noise: int, saveat=None,
                       rtol=1e-2, atol=1e-4, max_iters: int = 100_000,
                       event: Optional[Event] = None, lanes: bool = False,
                       depth: Optional[int] = None, order: float = 0.5,
                       nf_per_step: int = 1,
                       error_est: str = "doubling",
                       embedded: Optional[Callable] = None,
                       est_order: Optional[int] = None,
                       nf_per_attempt: Optional[int] = None,
                       controller: Optional["PIController"] = None,
                       bounded_steps: Optional[int] = None,
                       checkpoint_every: Optional[int] = None):
    """Adaptive SDE integration with per-element dt control and events.

    The missing half of the paper's "fully featured" claim for the SDE family:

    * **Local error** per attempted step, one of two estimators
      (``error_est``):

      - ``"embedded"`` — an embedded pair (`embedded`, e.g.
        `em_embedded_step`): ONE pass over the interval returns the
        propagated solution plus a companion-difference error estimate.
        ~1 stepper evaluation and one Brownian-tree descent per attempt —
        the default for steppers that ship a pair (see `SDE_EMBEDDED`).
      - ``"doubling"`` — step doubling: integrate once with dt and once as
        two dt/2 substeps *driven by the same Brownian path*; their
        difference is the error estimate and the finer solution propagates
        (local extrapolation).  Three stepper evaluations and two descents
        per attempt, but works for every registered stepper — no per-method
        pair needed.  Kept as the A/B reference and the general-noise path.
    * **Rejection-safe noise** (RSwM property): increments come from the
      virtual Brownian tree (`repro.kernels.rng.brownian_bridge_point`) — a
      pure function of (seed; lane, row, dyadic time) — so a rejected step
      retried with smaller dt sees exactly the same path, bitwise, on every
      strategy and backend.  Step sizes are quantized to whole cells of the
      depth-D dyadic grid (D = `depth`, default `default_bridge_depth`); the
      doubling estimator additionally rounds to an EVEN cell count so its
      half-steps land on grid points.
    * **Events** run the shared machinery (`repro.core.events`) on the
      piecewise-linear path output, with per-lane termination masks.
      Terminal hits freeze the lane at the located event time; a
      non-terminal affect is applied at the event point and integration
      resumes from the dyadic grid cell that re-anchors the located event
      time (NOT the step's grid end — the rejection machinery makes the
      rewind free: the bridge replays W at the re-anchored index bitwise).
    * **saveat** dense output: snapshots land on an arbitrary time grid via
      linear interpolation over accepted steps.

    `est_order` is the dt-order of the error estimator (PI controller
    exponents); `nf_per_attempt` the drift-evaluation count charged to `nf`
    per attempted step (defaults: 3 stepper evaluations for doubling, the
    `SDE_EMBEDDED` entry for pairs).

    Shape contract (same as the ERK engine): lanes=False integrates one
    trajectory u0 (n,) with scalar control and a scalar `lane_idx` (the
    trajectory's GLOBAL index — the RNG stream key); lanes=True integrates
    u0 (n, B) with per-lane control and lane_idx (B,).  Returns SolveResult,
    or (SolveResult, {"event_t", "event_count"}) when an event is supplied.

    ``bounded_steps``/``checkpoint_every`` select the reverse-differentiable
    bounded loop (`repro.core.loops.solver_loop`), enabling pathwise
    gradients through the accepted step sequence.  The step-size chain here
    is ALREADY gradient-frozen by construction — dt is consumed through a
    uint32 grid-cell count, and the Brownian increments are pure functions
    of integer indices, so vjp recomputation replays the virtual tree
    bitwise; the only extra severing needed is ``stop_gradient`` on the
    error norm (zero-cotangent sqrt hazard).  Too-small bound surfaces as
    ``status == 1``.
    """
    dtype = u0.dtype
    if error_est not in ("embedded", "doubling"):
        raise ValueError(f"unknown error_est {error_est!r} "
                         "(use 'embedded' or 'doubling')")
    use_pair = error_est == "embedded"
    if use_pair and embedded is None:
        raise ValueError("error_est='embedded' needs an embedded pair fn "
                         "(see repro.core.sde.SDE_EMBEDDED)")
    if est_order is None:
        est_order = max(1, int(round(order)))
    if nf_per_attempt is None:
        nf_per_attempt = 3 * nf_per_step
    ctrl = controller or PIController.for_order(int(est_order))
    cshape = (u0.shape[-1],) if lanes else ()
    axes = 0 if lanes else None
    t0 = jnp.asarray(t0, dtype)
    tf = jnp.asarray(tf, dtype)
    if depth is None:
        raise ValueError("sde_solve_adaptive needs a static `depth` "
                         "(see default_bridge_depth)")
    n_total = 2 ** depth
    h_res = (tf - t0) / n_total
    t_total = tf - t0

    from repro.kernels.rng import brownian_bridge_point

    if lanes:
        B = u0.shape[-1]
        lane_m = jnp.broadcast_to(
            jnp.asarray(lane_idx, jnp.uint32)[None, :], (m_noise, B))
        rows = jax.lax.broadcasted_iota(jnp.uint32, (m_noise, B), 0)

        def w_at(idx_c):                      # (B,) grid index -> (m, B)
            return brownian_bridge_point(
                seed, jnp.broadcast_to(idx_c[None, :], (m_noise, B)), lane_m,
                rows, depth=depth, t_total=t_total, dtype=dtype)
    else:
        lane_m = jnp.full((m_noise,), jnp.asarray(lane_idx, jnp.uint32))
        rows = jnp.arange(m_noise, dtype=jnp.uint32)

        def w_at(idx_c):                      # scalar grid index -> (m,)
            return brownian_bridge_point(
                seed, jnp.full((m_noise,), idx_c), lane_m, rows, depth=depth,
                t_total=t_total, dtype=dtype)

    if saveat is None:
        saveat = jnp.asarray([tf], dtype)
    saveat = jnp.asarray(saveat, dtype)
    S = saveat.shape[0]
    us0 = jnp.zeros((S,) + u0.shape, dtype)
    pre = (saveat <= t0).reshape((S,) + (1,) * u0.ndim)
    us0 = jnp.where(pre, u0[None], us0)

    n_total_u = jnp.asarray(n_total, jnp.uint32)
    nshape = (m_noise,) + cshape
    carry0 = dict(
        w_l=jnp.zeros(nshape, dtype),        # W(idx): W(0) = 0 exactly
        idx=jnp.zeros(cshape, jnp.uint32), u=u0,
        dt=jnp.broadcast_to(jnp.asarray(dt0, dtype), cshape),
        enorm_prev=jnp.ones(cshape, dtype),
        done=jnp.zeros(cshape, bool), us=us0,
        t_out=jnp.broadcast_to(t0, cshape),
        naccept=jnp.zeros(cshape, jnp.int32),
        nreject=jnp.zeros(cshape, jnp.int32),
        nf=jnp.zeros(cshape, jnp.int32),
        status=jnp.zeros(cshape, jnp.int32),
        iters=jnp.asarray(0, jnp.int32),
        event_t=jnp.full(cshape, jnp.inf, dtype),
        event_count=jnp.zeros(cshape, jnp.int32),
    )

    def cond(c):
        return (c["iters"] < max_iters) & jnp.any(~c["done"])

    def body(c):
        u, dt = c["u"], c["dt"]
        active = ~c["done"]
        idx = jnp.where(active, c["idx"], jnp.zeros_like(c["idx"]))
        t = t0 + idx.astype(dtype) * h_res
        # quantize the proposed dt to whole dyadic grid cells; the doubling
        # estimator needs an EVEN count so its half-steps land on grid points
        want = (jnp.minimum(dt, t_total) / h_res).astype(jnp.uint32)
        min_cells = jnp.uint32(1 if use_pair else 2)
        # resolution floor: the controller asked for < min_cells cells — no
        # finer path information exists at this depth, so the step
        # force-accepts (raise `depth`/brownian_depth for tighter tolerances)
        at_floor = want < min_cells
        m = (want if use_pair else (want >> 1) << 1)
        m = jnp.clip(m, min_cells, n_total_u - idx)
        dt_step = m.astype(dtype) * h_res

        # W at the left endpoint is carried from the previous iteration (it
        # equals last step's right endpoint on accept and is unchanged on
        # reject — the bridge is a pure function of idx, so this is exact,
        # and it saves one tree descent per attempted step)
        w_l = c["w_l"]
        w_r = w_at(idx + m)
        dWf = w_r - w_l

        if use_pair:
            # embedded pair: one pass gives the propagated solution AND the
            # companion-difference error — no midpoint descent, no half steps
            u_2, err = embedded(f, g, u, p, t, dt_step, dWf, noise)
        else:
            mh = m >> 1
            dt_half = mh.astype(dtype) * h_res
            t_mid = t0 + (idx + mh).astype(dtype) * h_res
            w_m = w_at(idx + mh)
            dW1, dW2 = w_m - w_l, w_r - w_m
            # one coarse step vs two half steps on the SAME path; keep finer
            u_c = stepper(f, g, u, p, t, dt_step, dWf, noise)
            u_h = stepper(f, g, u, p, t, dt_half, dW1, noise)
            u_2 = stepper(f, g, u_h, p, t_mid, dt_half, dW2, noise)
            # Richardson: the raw difference understates the error of the
            # PROPAGATED (finer) solution by (2^q - 1), q the stepper's
            # strong order — rescale so both estimators target the same
            # local error for the solution they actually advance
            err = (u_2 - u_c) * (1.0 / (2.0 ** order - 1.0))
        enorm = hairer_norm(err, u, u_2, atol, rtol, axes=axes)
        if bounded_steps is not None:
            # pathwise discrete adjoint: the controller chain is primal-only
            # (dt is consumed via an integer cell count anyway); this severs
            # the hairer_norm sqrt from the transpose so a zero local error
            # cannot inject NaN through sqrt'(0)
            enorm = jax.lax.stop_gradient(enorm)
        finite = jnp.isfinite(u_2)
        finite = jnp.all(finite, axis=0) if lanes else jnp.all(finite)
        accept = ((enorm <= 1.0) | at_floor) & finite & active
        dt_next, enorm_prev = pi_propose(ctrl, dt_step, enorm,
                                         c["enorm_prev"], accept)

        idx_new = jnp.where(accept, idx + m, idx)
        t_new = t0 + idx_new.astype(dtype) * h_res

        if event is not None:
            def interp_fn(theta):
                return linear_interp(u, u_2, theta, lanes=lanes)

            u_next, t_ev, ev_t, ev_n, term = handle_event(
                event, interp_fn, u, u_2, p, t, dt_step, t_new, accept,
                c["event_t"], c["event_count"], lanes=lanes)
            # non-terminal hit: the affected state lives at the located event
            # time t_ev, NOT the step's grid end — re-anchor onto the dyadic
            # grid (first cell boundary at/after t_ev) so integration resumes
            # where the affect was applied.  The rewind is free: the Brownian
            # tree replays W at the re-anchored index bitwise (the same
            # machinery that makes rejected steps exact).
            hit_nt = (ev_n > c["event_count"]) & ~term
            cells = jnp.clip(
                jnp.ceil((t_ev - t) / h_res - 1e-6).astype(jnp.uint32),
                jnp.uint32(1), m)
            idx_new = jnp.where(hit_nt, idx + cells, idx_new)
            t_new = t0 + idx_new.astype(dtype) * h_res
        else:
            u_next = u_2
            t_ev = t_new
            ev_t, ev_n = c["event_t"], c["event_count"]
            term = jnp.zeros(cshape, bool)
            hit_nt = term

        acc_e = accept[None] if lanes else accept
        u_new = jnp.where(acc_e, u_next, u)
        # reported time: located event time for terminal hits, grid otherwise
        t_out = jnp.where(term, t_ev, jnp.where(accept, t_new, c["t_out"]))
        t_lim = jnp.where(term, t_ev, t_new)

        # ---- linear dense save on the accepted step ------------------------
        eps = jnp.asarray(1e-7, dtype) * jnp.maximum(jnp.abs(t_lim), 1.0)
        if lanes:
            crossed = ((saveat[:, None] > t[None, :])
                       & (saveat[:, None] <= t_lim[None, :] + eps[None, :])
                       & accept[None, :])
            theta = jnp.clip((saveat[:, None] - t[None, :])
                             / dt_step[None, :], 0.0, 1.0)
            vals = u[None] + theta[:, None, :] * (u_2 - u)[None]
            us = jnp.where(crossed[:, None, :], vals, c["us"])
        else:
            crossed = (saveat > t) & (saveat <= t_lim + eps) & accept
            theta = jnp.clip((saveat - t) / dt_step, 0.0, 1.0)
            sh = (S,) + (1,) * u0.ndim
            vals = u[None] + theta.reshape(sh) * (u_2 - u)[None]
            us = jnp.where(crossed.reshape(sh), vals, c["us"])

        # rejecting at the dyadic resolution floor (can only mean non-finite
        # states there — at_floor otherwise force-accepts) or with dt pinned
        # at the controller floor: the retry is bit-identical, so terminate
        # the lane with a distinct status instead of spinning to max_iters
        hopeless = (active & ~accept
                    & (at_floor | ~(dt_step > ctrl.dtmin)))
        statusv = jnp.where(hopeless,
                            jnp.asarray(STATUS_DTMIN_EXHAUSTED, jnp.int32),
                            c["status"])
        done = c["done"] | term | (idx_new >= n_total_u) | hopeless
        acc_m = accept[None] if lanes else accept
        w_l_new = jnp.where(acc_m, w_r, w_l)
        if event is not None:
            # re-anchored lanes restart mid-step: their left-endpoint W is at
            # idx_new, not idx + m.  In lanes mode the scalar any() predicate
            # makes lax.cond a real branch — the extra descent is paid only
            # on iterations where a non-terminal event actually fired.  In
            # scalar mode (vmapped per-trajectory) the predicate is batched
            # and cond would lower to select anyway, so compute it directly.
            hit_m = hit_nt[None] if lanes else hit_nt

            def _refresh():
                return jnp.where(hit_m, w_at(idx_new), w_l_new)

            if lanes:
                w_l_new = jax.lax.cond(jnp.any(hit_nt), _refresh,
                                       lambda: w_l_new)
            else:
                w_l_new = _refresh()
        return dict(
            w_l=w_l_new,
            idx=idx_new, u=u_new, dt=dt_next, enorm_prev=enorm_prev,
            done=done, us=us, t_out=t_out,
            naccept=c["naccept"] + accept.astype(jnp.int32),
            nreject=c["nreject"] + (active & ~accept).astype(jnp.int32),
            nf=c["nf"] + active.astype(jnp.int32) * nf_per_attempt,
            status=statusv, iters=c["iters"] + 1,
            event_t=ev_t, event_count=ev_n)

    out = solver_loop(cond, body, carry0, bounded_steps=bounded_steps,
                      checkpoint_every=checkpoint_every)
    res = SolveResult(
        ts=saveat, us=out["us"], t_final=out["t_out"], u_final=out["u"],
        naccept=out["naccept"], nreject=out["nreject"],
        status=jnp.where(out["status"] > 0, out["status"],
                         jnp.where(out["done"], 0, 1)).astype(jnp.int32),
        nf=out["nf"], iters=out["iters"])
    if event is not None:
        return res, dict(event_t=out["event_t"], event_count=out["event_count"])
    return res


def solve_sde_ensemble(eprob: EnsembleProblem, key, dt, n_steps=None,
                       method="em", ensemble="kernel", backend="xla",
                       save_every=1, t0=None, tf=None,
                       lane_tile=None) -> "EnsembleSDEResult":
    """Legacy SDE-facing wrapper over the unified front door
    (`repro.core.ensemble.solve_ensemble_local`): same fixed-dt kernels
    (paper §5.2.2), dispatched through the method registry, result adapted to
    the SDE-shaped tuple.  New code should call the front door directly with
    ``alg=method``."""
    from .ensemble import solve_ensemble_local

    res = solve_ensemble_local(
        eprob, alg=method, ensemble=ensemble, backend=backend, t0=t0, tf=tf,
        dt0=dt, n_steps=n_steps, save_every=save_every, lane_tile=lane_tile,
        key=key)
    return EnsembleSDEResult(ts=res.ts, us=res.us, u_final=res.u_final,
                             nf=res.nf)


class EnsembleSDEResult(NamedTuple):
    ts: Array
    us: Array        # (N, S, n)
    u_final: Array   # (N, n)
    nf: Array
