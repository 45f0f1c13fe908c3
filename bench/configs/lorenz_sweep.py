"""Lorenz parameter sweep, the DiffEqGPU paper's headline ODE benchmark.

Configuration `lorenz_sweep.json` beside this file: sigma = 10, beta = 8/3,
u0 = (1, 0, 0), t in [0, 1], rho drawn uniformly from (0, 21), float32,
Tsit5 through the fused Pallas kernel.  The traffic file of a cell gives
the ensemble size and the stepping (fixed dt or rtol/atol with saves).

What this module gives the harness:

* `make_inputs` — (u0s, ps) on the device from the seed, in one jitted call;
* `solver` — the timed call through the program's front door;
* `check` — the timed solve's saved states on a seed-drawn sample of
  lanes against a plain float64 RK4 reference (`reference`), with the
  non-finite count and the solver status over every lane;
* `control` — that reference's method put in the program's place, a plain
  Tsit5 at the cell's dt0 in bfloat16, which `check` has to refuse;
* `work` — operations per attempted lane-step and per save, and HBM bytes
  per trajectory, by the terms listed at the counts below.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench.checks import sample_lanes, scaled_err

CONFIG = json.loads(Path(__file__).with_suffix(".json").read_text())
F32 = jnp.float32

# Tsitouras 5(4) tableau (Tsitouras 2011; coefficients as OrdinaryDiffEq.jl
# prints them).  Row i gives stage i+2's weights on k_1..k_{i+1}; the last
# row is b, so stage 7's argument is the step's new state (FSAL).
TSIT5_A = (
    (0.161,),
    (-0.008480655492356989, 0.335480655492357),
    (2.8971530571054935, -6.359448489975075, 4.3622954328695815),
    (5.325864828439257, -11.748883564062828, 7.4955393428898365,
     -0.09249506636175525),
    (5.86145544294642, -12.92096931784711, 8.159367898576159,
     -0.071584973281401006, -0.028269050394068383),
    (0.09646076681806523, 0.01, 0.4798896504144996, 1.379008574103742,
     -3.290069515436081, 2.324710524099774),
)
# b - bhat: error = dt * sum(btilde_i k_i), all seven stages
TSIT5_BTILDE = (-0.00178001105222577714, -0.0008164344596567469,
                0.007880878010261995, -0.1447110071732629,
                0.5823571654525552, -0.45808210592918697,
                0.015151515151515152)
# PI controller of an order-4 embedded pair: beta1 = 0.7/5, beta2 = 0.4/5
BETA1, BETA2, SAFETY, QMIN, QMAX = 0.14, 0.08, 0.9, 0.2, 10.0

# --- operation counts (rule: bench/workcount.py) ------------------------------
N_STATE = 3
OPS_RHS = (2     # sigma * (y - x)
           + 4   # rho * x - y - x * z
           + 3)  # x * y - beta * z
# stage i+2 with m weights: m multiplies, m - 1 adds, one dt multiply and
# one add of u, per state component; m runs 1..6
OPS_STAGE_ARGS = N_STATE * sum(2 * m + 1 for m in range(1, 7))
OPS_FIXED_STEP = (6 * OPS_RHS        # stages 2..7 (FSAL: k1 is the last k7)
                  + OPS_STAGE_ARGS
                  + 1)               # t + dt
OPS_ADAPTIVE_ATTEMPT = (
    2                                # h = min(dt, tf - t)
    + 6 * OPS_RHS + OPS_STAGE_ARGS   # the stages, as in a fixed step
    + N_STATE * (7 + 6 + 1)          # err = h * sum of 7 btilde_i k_i
    + N_STATE * 5                    # atol + max(|u|, |u_new|) * rtol
    + N_STATE                        # q = err / scale
    + N_STATE + 2 + 1 + 1            # sqrt(sum(q * q) / 3)
    + 1                              # accept = enorm <= 1
    + 1                              # e = max(enorm, 1e-10)
    + 1                              # e ** -beta1
    + 5                              # clip(safety * e^-b1 * prev ** b2)
    + 3                              # clip(safety * e^-b1, qmin, 1)
    + 2                              # dt * where(accept, ...)
    + 1                              # enorm_prev = where(accept, e, prev)
    + 2                              # t = where(accept, t + h, t)
    + 2 * N_STATE                    # u and k1 = where(accept, new, old)
    + 1)                             # done = t >= tf
OPS_DENSE_SAVE = (
    2                                # theta = (ts - t) / h
    + 1                              # theta ** 2
    + 7 + 6 * 5                      # the seven b_i(theta) polynomials
    + N_STATE * (7 + 6 + 1 + 1))     # u + h * sum of 7 b_i k_i


def rhs(u, p):
    """The Lorenz right-hand side, per lane: u (3, ...), p (sigma, rho,
    beta), each a scalar or a lane vector."""
    x, y, z = u[0], u[1], u[2]
    sigma, rho, beta = p
    return jnp.stack([sigma * (y - x), rho * x - y - x * z, x * y - beta * z])


def tsit5_stages(u, p, h, k1):
    """Stages 2..7 of one Tsit5 step; returns (u_new, [k1..k7])."""
    ks = [k1]
    ui = u
    for row in TSIT5_A:
        acc = row[0] * ks[0]
        for a, k in zip(row[1:], ks[1:]):
            acc = acc + a * k
        ui = u + h * acc
        ks.append(rhs(ui, p))
    return ui, ks


def plain_fixed_step(u, p, t, dt, k1):
    """One fixed Tsit5 step of one lane; k1 = rhs(u) carried (FSAL)."""
    u_new, ks = tsit5_stages(u, p, dt, k1)
    return u_new, t + dt, ks[-1]


def plain_adaptive_attempt(u, p, t, dt, k1, enorm_prev, tf, rtol, atol):
    """One attempted Tsit5 step of one lane under the PI controller."""
    h = jnp.minimum(dt, tf - t)
    u_new, ks = tsit5_stages(u, p, h, k1)
    e = TSIT5_BTILDE[0] * ks[0]
    for bt, k in zip(TSIT5_BTILDE[1:], ks[1:]):
        e = e + bt * k
    err = h * e
    scale = atol + jnp.maximum(jnp.abs(u), jnp.abs(u_new)) * rtol
    q = err / scale
    enorm = jnp.sqrt(jnp.sum(q * q) / N_STATE)
    accept = enorm <= 1.0
    en = jnp.maximum(enorm, 1e-10)
    grow = en ** -BETA1
    fac_acc = jnp.minimum(jnp.maximum(SAFETY * grow * enorm_prev ** BETA2,
                                      QMIN), QMAX)
    fac_rej = jnp.minimum(jnp.maximum(SAFETY * grow, QMIN), 1.0)
    dt_next = dt * jnp.where(accept, fac_acc, fac_rej)
    prev_next = jnp.where(accept, en, enorm_prev)
    t_next = jnp.where(accept, t + h, t)
    u_next = jnp.where(accept, u_new, u)
    k1_next = jnp.where(accept, ks[-1], k1)
    done = t_next >= tf
    return u_next, t_next, dt_next, k1_next, prev_next, done


def plain_dense_save(u, h, t, ts, ks):
    """Tsitouras' free interpolant at save time ts inside the step."""
    th = (ts - t) / h
    t2 = th * th
    bs = (
        -1.0530884977290216 * th * (th - 1.3299890189751412)
        * (t2 - 1.4364028541716351 * th + 0.7139816917074209),
        0.1017 * t2 * (t2 - 2.1966568338249754 * th + 1.2949852507374631),
        2.490627285651252793 * t2
        * (t2 - 2.38535645472061657 * th + 1.57803468208092486),
        -16.54810288924490272 * (th - 1.21712927295533244)
        * (th - 0.61620406037800089) * t2,
        47.37952196281928122 * (th - 1.203071208372362603)
        * (th - 0.658047292653547382) * t2,
        -34.87065786149660974 * (th - 1.2) * (th - 0.6666666666666666) * t2,
        2.5 * (th - 1.0) * (th - 0.6) * t2,
    )
    acc = bs[0] * ks[0]
    for b, k in zip(bs[1:], ks[1:]):
        acc = acc + b * k
    return u + h * acc


# --- the cell -----------------------------------------------------------------

def save_times(traffic) -> list:
    if traffic["adaptive"]:
        return list(traffic["saveat"])
    every = traffic["dt"] * traffic["save_every"]
    k = traffic["n_steps"] // traffic["save_every"]
    return [CONFIG["t0"] + every * (i + 1) for i in range(k)]


def make_inputs(key_words, n, sharding=None):
    """(u0s (n, 3), ps (n, 3)): rho uniform over (rho_min, rho_max) in
    random lane order, drawn on the device from two key words."""
    c = CONFIG

    def build(words):
        key = jax.random.wrap_key_data(words)
        rho = jax.random.uniform(key, (n,), F32, c["rho_min"], c["rho_max"])
        u0s = jnp.broadcast_to(jnp.asarray(c["u0"], F32), (n, 3))
        ps = jnp.stack([jnp.full((n,), c["sigma"], F32), rho,
                        jnp.full((n,), c["beta"], F32)], axis=1)
        return u0s, ps

    return jax.jit(build, out_shardings=sharding)(key_words)


def solver(traffic, n, mesh=None):
    """The timed call: the whole ensemble through the program's front
    door, fused Pallas kernel, on one device or sharded over `mesh`."""
    from repro.configs.de_problems import lorenz_problem
    from repro.core import EnsembleProblem, solve_ensemble_local
    from repro.core.api import solve_ensemble

    c = CONFIG
    prob = lorenz_problem(F32)
    kw = dict(alg=c["method"], ensemble="kernel", backend="pallas",
              t0=c["t0"], tf=c["tf"])
    if traffic["adaptive"]:
        kw.update(dt0=traffic["dt0"], rtol=traffic["rtol"],
                  atol=traffic["atol"],
                  saveat=np.asarray(traffic["saveat"], np.float32))
    else:
        kw.update(dt0=traffic["dt"], adaptive=False,
                  n_steps=traffic["n_steps"],
                  save_every=traffic["save_every"])

    def solve(u0s, ps):
        ep = EnsembleProblem(prob, n, u0s=u0s, ps=ps)
        if mesh is None:
            r = solve_ensemble_local(ep, **kw)
        else:
            r = solve_ensemble(ep, mesh=mesh, **kw)
        return dict(us=r.us, naccept=r.naccept, nreject=r.nreject, nf=r.nf,
                    status=r.status)

    return solve


def control(traffic, n, dtype=jnp.bfloat16):
    """The plain Tsit5 of this module at the cell's dt (dt0 when adaptive),
    fixed steps, every lane at once, in `dtype`: the lower-precision step
    a later change might be tempted by.  Same outputs as `solver`."""
    dt = traffic["dt0"] if traffic["adaptive"] else traffic["dt"]
    t0 = CONFIG["t0"]
    ts = [t0] + save_times(traffic)
    steps = [int(round((b - a) / dt)) for a, b in zip(ts[:-1], ts[1:])]

    def solve(u0s, ps):
        u = u0s.T.astype(dtype)
        p = tuple(ps.T.astype(dtype))
        h = jnp.asarray(dt, dtype)
        t = jnp.asarray(t0, dtype)
        k1 = rhs(u, p)
        saves = []
        for m in steps:
            def body(_, c):
                return plain_fixed_step(c[0], p, c[1], h, c[2])
            u, t, k1 = jax.lax.fori_loop(0, m, body, (u, t, k1))
            saves.append(u)
        us = jnp.stack(saves).astype(F32)          # (S, 3, n)
        return dict(us=jnp.moveaxis(us, -1, 0), status=jnp.asarray(0))

    return solve


def reference(u0s, ps, t_saves, dt=1e-4):
    """NumPy float64 classical RK4 on the given trajectories: (K, S, 3)."""
    u = np.asarray(u0s, np.float64).T.copy()
    s, r, b = np.asarray(ps, np.float64).T

    def f(v):
        x, y, z = v
        return np.stack([s * (y - x), r * x - y - x * z, x * y - b * z])

    save_steps = [int(round(t / dt)) for t in t_saves]
    out = []
    for k in range(1, save_steps[-1] + 1):
        k1 = f(u)
        k2 = f(u + 0.5 * dt * k1)
        k3 = f(u + 0.5 * dt * k2)
        k4 = f(u + dt * k3)
        u = u + (dt / 6.0) * (k1 + 2.0 * k2 + 2.0 * k3 + k4)
        if k in save_steps:
            out.append(u.T.copy())
    return np.stack(out, axis=1)


def check(out, inputs, traffic, seed):
    """The numbers compared, as (name, value): the widest scaled error of a
    saved state on the seed's sample of lanes, the count of non-finite
    saved values over all lanes, and the largest solver status."""
    u0s, ps = inputs
    us = out["us"]
    n = us.shape[0]
    idx = sample_lanes(n, seed)
    got = np.asarray(jax.device_get(us[idx]), np.float64)
    ref = reference(jax.device_get(u0s[idx]), jax.device_get(ps[idx]),
                    save_times(traffic))
    nonfinite = int(jax.device_get(jnp.sum(~jnp.isfinite(us))))
    return [("max_scaled_err", scaled_err(got, ref)),
            ("nonfinite", float(nonfinite)),
            ("status_max", float(jax.device_get(out["status"])))]


def work(traffic):
    """Algorithmic work, counted per useful lane-step (bench/workcount.py)
    and bytes of HBM traffic per trajectory (state and parameters read
    once, saves, final state and time and six stats words written once)."""
    s = len(save_times(traffic))
    if traffic["adaptive"]:
        per_attempt, per_save = OPS_ADAPTIVE_ATTEMPT, OPS_DENSE_SAVE
    else:
        # saves land on the step grid: the save is the step's own state
        per_attempt, per_save = OPS_FIXED_STEP, 0
    return dict(ops_per_attempt=per_attempt, ops_per_save=per_save,
                saves=s, bytes_per_traj=4 * (3 + 3 + 3 * s + 3 + 1 + 6))
