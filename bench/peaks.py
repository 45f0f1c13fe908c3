"""The table of device peaks, and the microkernel that measures the vector peak.

`peaks.json` beside this file is keyed by `device_kind`; each entry gives
the f32 vector rate measured by `measure_vector_peak` on that device, the
published HBM bandwidth, and the source of each.  A device missing from the
table is an error.

The microkernel keeps `chains` independent multiply-add chains, each one
full (8, 128) float32 tile held in vector registers, and runs a loop whose
every iteration makes `unroll` updates of every chain, ``x = x * a + b``,
`UPDATES` updates in all: two operations per element per update, counted by
the rule of `bench/workcount.py`.  The best rate over a few shapes is the
peak.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

TABLE = Path(__file__).with_name("peaks.json")
TILE = (8, 128)
# (chains, unroll) shapes tried; the best rate wins
SHAPES = ((16, 8), (20, 8), (16, 16), (20, 16), (24, 16))
UPDATES = 1 << 23          # per chain: ~50 ms a call at 5e12 op/s


def lookup(device_kind: str) -> dict:
    table = json.loads(TABLE.read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {TABLE.name}; "
                       "measure its vector peak and add it")
    return table[device_kind]


def _kernel(chains: int, unroll: int, iters: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def body(a_ref, b_ref, o_ref):
        a, b = a_ref[...], b_ref[...]
        xs = tuple(a * (1.0 + 0.001 * i) for i in range(chains))

        def step(_, xs):
            for _ in range(unroll):
                xs = tuple(x * a + b for x in xs)
            return xs

        xs = jax.lax.fori_loop(0, iters, step, xs)
        acc = xs[0]
        for x in xs[1:]:
            acc = acc + x
        o_ref[...] = acc

    return jax.jit(pl.pallas_call(
        body, out_shape=jax.ShapeDtypeStruct(TILE, jnp.float32)))


def ops(chains: int, unroll: int, iters: int) -> int:
    return chains * unroll * iters * 2 * TILE[0] * TILE[1]


def measure_vector_peak(repeats: int = 3, shapes=SHAPES,
                        updates: int = UPDATES) -> dict:
    """Best f32 vector operations per second over `shapes`, each timed
    `repeats` times after a warm-up call; returns the rate of each shape."""
    import jax
    import jax.numpy as jnp

    a = jnp.full(TILE, 0.999, jnp.float32)
    b = jnp.full(TILE, 1e-3, jnp.float32)
    rates = {}
    for chains, unroll in shapes:
        iters = updates // unroll
        f = _kernel(chains, unroll, iters)
        jax.block_until_ready(f(a, b))
        best = float("inf")
        for _ in range(repeats):
            t = time.perf_counter()
            jax.block_until_ready(f(a, b))
            best = min(best, time.perf_counter() - t)
        rates[f"{chains}x{unroll}"] = ops(chains, unroll, iters) / best
    return dict(best=max(rates.values()), rates=rates)
