"""Geometric Brownian motion Monte Carlo, the DiffEqGPU paper's SDE benchmark.

Configuration `gbm_mc.json` beside this file: three independent GBM states,
r = 1.5, v = 0.01, u0 = 0.1, t in [0, 1], float32, Euler-Maruyama through
the fused Pallas kernel with the noise drawn in the kernel.

The noise of path i, state row j, step k is the Box-Muller normal of the
Threefry-2x32 words of counter (k * 0x9E3779B9 + j, i) under the key
(key0, key1) of the configuration.  The seed of a run chooses the global
index of its first path, so each seed draws its own window of the stream
and one compiled kernel serves them all.

`check` replays, in float64 NumPy with a Threefry written here, every
sampled path's whole recursion, and compares the ensemble mean over every
path with EM's exact mean.  `control` is that recursion on the device in
bfloat16.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench.checks import sample_lanes, rel_err

CONFIG = json.loads(Path(__file__).with_suffix(".json").read_text())
F32 = jnp.float32
ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
PARITY = 0x1BD11BDA
MASK = 0xFFFFFFFF

# --- operation counts (rule: bench/workcount.py) ------------------------------
N_STATE = 3
OPS_THREEFRY = (2                   # counters + key words
                + 20 * (1 + 3 + 1)  # 20 rounds: add, rotate (2 shifts, or), xor
                + 5 * 2)            # 5 key injections: one add per word
OPS_NORMAL = (1                     # c0 = step * M + row (row per element)
              + OPS_THREEFRY
              + 2 * 3               # two uniforms: convert, + 0.5, * 2^-32
              + 6)                  # sqrt(-2 log u1) * cos(2 pi u2)
OPS_EM_STEP = (1                    # step * M, once per lane-step
               + N_STATE * OPS_NORMAL
               + N_STATE * (1       # dW = z * sqrt(dt)
                            + 2     # r * u * dt
                            + 2     # v * u * dW
                            + 2))   # u + drift + diffusion


def threefry2x32(k0, k1, c0, c1):
    """Threefry-2x32, 20 rounds (Salmon et al. 2011), on uint32 arrays of
    NumPy or jax.numpy; k0, k1 Python ints."""
    ks = (k0, k1, k0 ^ k1 ^ PARITY)
    x0 = c0 + np.uint32(ks[0])
    x1 = c1 + np.uint32(ks[1])
    for i in range(5):
        for r in ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = (x1 << np.uint32(r)) | (x1 >> np.uint32(32 - r))
            x1 = x1 ^ x0
        x0 = x0 + np.uint32(ks[(i + 1) % 3])
        x1 = x1 + np.uint32((ks[(i + 2) % 3] + i + 1) & MASK)
    return x0, x1


def plain_normal(step, row, lane, xp=jnp, dtype=F32):
    """The stream's N(0, 1) draw for (step, row, lane), uint32 inputs."""
    s = CONFIG["stream"]
    c0 = step * np.uint32(s["step_mult"]) + row
    w0, w1 = threefry2x32(s["key0"], s["key1"], c0, lane)
    u1 = (w0.astype(dtype) + 0.5) * 2.0 ** -32
    u2 = (w1.astype(dtype) + 0.5) * 2.0 ** -32
    return xp.sqrt(-2.0 * xp.log(u1)) * xp.cos(2.0 * np.pi * u2)


def plain_em_step(u, step, lane, r, v, dt, xp=jnp, dtype=F32):
    """One Euler-Maruyama step of one path (u of shape (3,) or (3, K)):
    u + r u dt + v u z sqrt(dt)."""
    rows = xp.arange(N_STATE, dtype=np.uint32)
    if xp is jnp:
        rows = rows.reshape((N_STATE,) + (1,) * (u.ndim - 1))
    else:
        rows = rows.reshape((N_STATE,) + (1,) * (np.ndim(u) - 1))
    lanes = xp.broadcast_to(lane, xp.broadcast_shapes(rows.shape,
                                                      xp.shape(lane)))
    z = plain_normal(step, rows, lanes, xp, dtype)
    dw = z * float(np.sqrt(dt))
    return u + r * u * dt + v * u * dw


# --- the cell -----------------------------------------------------------------

def lane_offset(key_words) -> int:
    """The global index of the run's first path, from the seed's words."""
    return int(np.asarray(key_words, np.uint32)[0])


def make_inputs(key_words, n, sharding=None):
    """(u0s (n, 3), ps (n, 2), offset uint32): the configuration's u0 and
    (r, v) on every path, and the seed's first global lane."""
    c = CONFIG

    def build(words):
        u0s = jnp.broadcast_to(jnp.asarray(c["u0"], F32), (n, 3))
        ps = jnp.broadcast_to(jnp.asarray([c["r"], c["v"]], F32), (n, 2))
        return u0s, ps, words[0]

    return jax.jit(build, out_shardings=sharding)(key_words)


def solver(traffic, n, mesh=None):
    """The timed call: Euler-Maruyama over every path in the fused Pallas
    kernel, noise drawn in the kernel from the configuration's key."""
    if mesh is not None:
        raise NotImplementedError("gbm_mc has no sharded cell")
    from repro.configs.de_problems import gbm_problem
    from repro.core import EnsembleProblem, solve_ensemble_local

    c = CONFIG
    prob = gbm_problem(r=c["r"], v=c["v"], dtype=F32)
    kw = dict(alg=c["method"], ensemble="kernel", backend="pallas",
              t0=c["t0"], tf=c["tf"], dt0=traffic["dt"],
              n_steps=traffic["n_steps"], save_every=traffic["save_every"],
              seed=c["stream"]["key0"])

    def solve(u0s, ps, offset):
        r = solve_ensemble_local(EnsembleProblem(prob, n, u0s=u0s, ps=ps),
                                 lane_offset=offset, **kw)
        return dict(u_final=r.u_final, naccept=r.naccept, nreject=r.nreject,
                    nf=r.nf, status=r.status)

    return solve


def control(traffic, n, dtype=jnp.bfloat16):
    """This module's plain recursion on every path at once, in `dtype`."""
    dt = traffic["dt"]

    def solve(u0s, ps, offset):
        lane = offset + jnp.arange(n, dtype=jnp.uint32)
        u = u0s.T.astype(dtype)
        r = ps[:, 0].astype(dtype)
        v = ps[:, 1].astype(dtype)

        def body(k, u):
            return plain_em_step(u, k.astype(jnp.uint32), lane, r, v,
                                 dt, jnp, dtype).astype(dtype)

        u = jax.lax.fori_loop(0, traffic["n_steps"], body, u)
        return dict(u_final=u.T.astype(F32), status=jnp.asarray(0))

    return solve


def reference(lanes, traffic):
    """Float64 replay of the paths with these global lane indices: (K, 3)."""
    c = CONFIG
    lanes = np.asarray(lanes, np.uint32)[None, :]
    u = np.broadcast_to(np.asarray(c["u0"], np.float64)[:, None],
                        (N_STATE, lanes.shape[1])).copy()
    with np.errstate(over="ignore"):   # uint32 counters wrap by design
        for k in range(traffic["n_steps"]):
            u = plain_em_step(u, np.uint32(k), lanes, c["r"], c["v"],
                              traffic["dt"], np, np.float64)
    return u.T


def em_mean(traffic) -> float:
    """EM's exact mean: E[u_{k+1}] = (1 + r dt) E[u_k]."""
    c = CONFIG
    return c["u0"][0] * (1.0 + c["r"] * traffic["dt"]) ** traffic["n_steps"]


def check(out, inputs, traffic, seed):
    """The numbers compared, as (name, value): the widest relative error of
    a sampled path's final state against its float64 replay, the distance
    of the ensemble mean from EM's exact mean in standard errors, and the
    count of non-finite final values."""
    _, _, offset = inputs
    uf = out["u_final"]
    n = uf.shape[0]
    idx = sample_lanes(n, seed)
    lanes = (np.uint64(int(jax.device_get(offset))) + idx.astype(np.uint64)) \
        & MASK
    got = np.asarray(jax.device_get(uf[idx]), np.float64)
    ref = reference(lanes, traffic)
    mean = em_mean(traffic)
    d = uf.astype(F32) - F32(mean)
    dev, var = jax.device_get((jnp.mean(d), jnp.mean(d * d)))
    se = float(np.sqrt(max(float(var) - float(dev) ** 2, 0.0) / d.size))
    z = abs(float(dev)) / se if se > 0 else float(np.inf)
    nonfinite = int(jax.device_get(jnp.sum(~jnp.isfinite(uf))))
    return [("path_rel_err", rel_err(got, ref)),
            ("mean_err_se", z if np.isfinite(z) else float(np.inf)),
            ("nonfinite", float(nonfinite))]


def work(traffic):
    """Algorithmic work per lane-step and HBM bytes per path (state and
    parameters read once; saves, final state, time and seven stats words
    written once)."""
    s = traffic["n_steps"] // traffic["save_every"]
    return dict(ops_per_attempt=OPS_EM_STEP, ops_per_save=0, saves=s,
                bytes_per_traj=4 * (3 + 2 + 3 * s + 3 + 1 + 7))
