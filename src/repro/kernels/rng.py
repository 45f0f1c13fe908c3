"""Counter-based RNG for inside-kernel noise generation (paper §6.8).

The paper's GPU kernels draw per-thread noise from a counter-based PRNG; the
TPU-native equivalent is `pltpu.prng_seed`/`prng_random_bits`, but that
primitive has no CPU/interpret lowering, so kernels default to a hand-rolled
**Threefry-2x32 (20 rounds)** — the same generator JAX itself uses — built from
32-bit adds/xors/rotates only (TPU-friendly, identical bits on every backend,
replayable from (seed, lane, step) counters).  `impl="tpu"` switches to the
hardware PRNG on real TPUs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
_PARITY = 0x1BD11BDA  # python int: kernels may not capture array constants


def _rotl(x, r):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def threefry2x32(k0, k1, c0, c1):
    """Threefry-2x32, 20 rounds. All args uint32 arrays (broadcastable).
    Returns two uint32 arrays of the broadcast shape."""
    ks0 = jnp.uint32(k0)
    ks1 = jnp.uint32(k1)
    ks2 = ks0 ^ ks1 ^ jnp.uint32(_PARITY)
    x0 = jnp.asarray(c0, jnp.uint32) + ks0
    x1 = jnp.asarray(c1, jnp.uint32) + ks1
    subkeys = ((ks1, ks2), (ks2, ks0), (ks0, ks1), (ks1, ks2), (ks2, ks0))
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, r)
            x1 = x1 ^ x0
        a, b = subkeys[i]
        x0 = x0 + a
        x1 = x1 + b + jnp.uint32(i + 1)
    return x0, x1


def _u32_to_f32(bits):
    """uint32 -> float32, correctly rounded, without a uint32->float convert
    (Mosaic has none).  Both 16-bit halves convert exactly and the one f32
    add rounds their exact sum once, so this equals ``bits.astype(f32)``."""
    hi = (bits >> jnp.uint32(16)).astype(jnp.int32).astype(jnp.float32)
    lo = (bits & jnp.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    return hi * jnp.float32(65536.0) + lo


def _to_unit(bits):
    """uint32 -> float in (0, 1): (bits + 0.5) / 2^32, exact in f32 range."""
    return (_u32_to_f32(bits) + 0.5) * jnp.float32(2.0 ** -32)


def bridge_normals(seed, node, lane_idx, row_idx, dtype=jnp.float32):
    """N(0,1) draws for the virtual Brownian bridge, indexed by
    (seed; tree-node, noise-row, lane).

    Same Threefry core as `counter_normals_threefry` but keyed with a
    different second key word, so the bridge stream is independent of the
    fixed-dt per-step stream under the same seed.
    """
    c0 = (jnp.asarray(node, jnp.uint32) * jnp.uint32(0x9E3779B9)
          + jnp.asarray(row_idx, jnp.uint32))
    c1 = jnp.asarray(lane_idx, jnp.uint32)
    x0, x1 = threefry2x32(jnp.uint32(seed), jnp.uint32(0x85A308D3), c0, c1)
    u1 = _to_unit(x0)
    u2 = _to_unit(x1)
    z = jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(2.0 * jnp.pi * u2)
    return z.astype(dtype)


def brownian_bridge_point(seed, idx, lane_idx, row_idx, *, depth, t_total,
                          dtype=jnp.float32):
    """W(idx * t_total / 2**depth) of a standard Wiener path on [0, t_total].

    The path is a *virtual Brownian tree* (Levy bridge construction, cf.
    RSwM / torchsde's BrownianTree): W is a pure function of
    (seed; lane, row, dyadic index), evaluated by descending `depth` levels of
    midpoint-conditioned draws.  Because the value at a grid point never
    depends on the *step sequence* that queried it, a rejected step replays
    exactly the same increments when retried with a smaller dt — bitwise, on
    every strategy and backend.  That is the property that makes adaptive SDE
    stepping cross-backend deterministic.

    idx: integer array (broadcastable against lane_idx/row_idx) in
         [0, 2**depth]; each element may name a different grid point (per-lane
         adaptive dt).
    Cost: `depth` Threefry evaluations per point.

    **Rejection/replay contract** (what the adaptive SDE engine and the
    property tests in `tests/test_bridge_props.py` rely on):

    1. W(idx) depends ONLY on (seed; lane, row, idx, depth, t_total) — never
       on query order, query shape, or any other index queried before or
       after.  Any reject -> shrink -> redraw sequence therefore replays the
       sub-interval increments bitwise, on every strategy and backend.
    2. W(0) == 0 exactly, and increments telescope exactly: for any grid
       partition i0 < i1 < ... < ik, sum of W(i_{j+1}) - W(i_j) equals
       W(ik) - W(i0) in floating point up to associativity of the sum.
    3. Conditionally on W(l) and W(r) for an enclosing dyadic interval
       [l, r], the midpoint is N((W(l)+W(r))/2, (t_r - t_l)/4) — the Levy
       bridge construction, which is what makes per-lane step sequences
       statistically consistent regardless of accept/reject history.
    """
    idx = jnp.asarray(idx, jnp.uint32)
    shape = jnp.broadcast_shapes(jnp.shape(idx), jnp.shape(lane_idx),
                                 jnp.shape(row_idx))
    idx = jnp.broadcast_to(idx, shape)
    lane_idx = jnp.broadcast_to(jnp.asarray(lane_idx, jnp.uint32), shape)
    row_idx = jnp.broadcast_to(jnp.asarray(row_idx, jnp.uint32), shape)
    t_total = jnp.asarray(t_total, dtype)
    h_res = t_total / (2 ** depth)           # grid resolution in time units
    # endpoint draw: W(t_total) ~ N(0, t_total), tree node 0
    w_l = jnp.zeros(shape, dtype)
    w_r = jnp.sqrt(t_total) * bridge_normals(seed, jnp.zeros(shape, jnp.uint32),
                                             lane_idx, row_idx, dtype)
    l = jnp.zeros(shape, jnp.uint32)
    r = jnp.full(shape, 2 ** depth, jnp.uint32)
    nid = jnp.ones(shape, jnp.uint32)        # heap id of the interval [l, r)

    def body(_, carry):
        l, r, nid, w_l, w_r = carry
        mid = (l + r) >> 1
        h = (r - l).astype(dtype) * h_res
        z = bridge_normals(seed, nid, lane_idx, row_idx, dtype)
        # midpoint conditioned on the endpoints: var = h/4
        w_mid = 0.5 * (w_l + w_r) + (0.5 * jnp.sqrt(h)) * z
        go_left = idx <= mid
        w_r = jnp.where(go_left, w_mid, w_r)
        w_l = jnp.where(go_left, w_l, w_mid)
        r = jnp.where(go_left, mid, r)
        l = jnp.where(go_left, l, mid)
        nid = 2 * nid + (~go_left).astype(jnp.uint32)
        return l, r, nid, w_l, w_r

    l, r, nid, w_l, w_r = jax.lax.fori_loop(0, depth, body,
                                            (l, r, nid, w_l, w_r))
    return jnp.where(idx == l, w_l, w_r)


def counter_normals_threefry(seed, step, lane_idx, row_idx, dtype=jnp.float32):
    """N(0,1) draws indexed by (seed; step, noise-row, lane) — one value per
    (row_idx, lane_idx) element via Box-Muller on two threefry words.

    lane_idx: (…,) global trajectory indices (uint32-able)
    row_idx:  (…,) noise-component indices, broadcastable against lane_idx.
    """
    c0 = (jnp.asarray(step, jnp.uint32) * jnp.uint32(0x9E3779B9)
          + jnp.asarray(row_idx, jnp.uint32))
    c1 = jnp.asarray(lane_idx, jnp.uint32)
    x0, x1 = threefry2x32(jnp.uint32(seed), jnp.uint32(0x243F6A88), c0, c1)
    u1 = _to_unit(x0)
    u2 = _to_unit(x1)
    z = jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(2.0 * jnp.pi * u2)
    return z.astype(dtype)
