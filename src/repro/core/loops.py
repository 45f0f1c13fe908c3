"""Bounded, checkpointed solver loops — the reverse-mode AD substrate.

`jax.lax.while_loop` is the right forward-mode shape for adaptive stepping
(it supports jvp, so forward sensitivities work out of the box) but it has no
transpose rule: reverse-mode AD cannot cross it.  Every adaptive engine body
in this repo is written so that a finished lane's iteration is an exact no-op
(all writes are masked by ``accept``/``active``), which buys the classic
substitution: run the SAME body for a fixed, static number of iterations and
the outputs are bitwise-identical to the while loop whenever the bound covers
the true iteration count — and a too-small bound surfaces as ``status == 1``
(max-iters semantics), never as silent wrong answers.

`solver_loop` is that substitution: with ``bounded_steps=None`` it IS
``lax.while_loop`` (the forward hot path, untouched); with an integer bound it
becomes a ``lax.scan`` over `jax.checkpoint`-wrapped segments of
``checkpoint_every`` body applications.  The scan is reverse-differentiable,
and the remat segments are the "periodic carry checkpoints" of the
checkpointed discrete adjoint: the forward pass stores one full carry
(u, t, dt, RNG counters, J/LU freshness — whatever the engine carries) per
segment boundary instead of per step, and the reverse pass recomputes each
segment from its checkpoint, so peak memory is
O(n_segments * carry + checkpoint_every * step_residuals) instead of
O(bounded_steps * step_residuals).

`checkpointed_fori` is the fixed-step sibling for ``fori_loop``-shaped paths
(the SDE reference kernel, the vmap fixed-dt SDE path).
"""
from __future__ import annotations

import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

Carry = Any


def default_checkpoint_every(bounded_steps: int) -> int:
    """sqrt-schedule: balances stored carries against recompute residuals."""
    return max(1, math.isqrt(max(1, int(bounded_steps))))


def bool_codec(template: Carry):
    """(encode, decode) between a carry and the same carry with its bool
    leaves held as int32.

    The TPU kernel compiler (Mosaic) cannot carry boolean vectors through a
    loop or a cond (it fails to legalize the ``scf.yield``), and the engines
    carry per-lane ``done``/``fresh`` masks.  Loops that run inside the Pallas
    kernels therefore carry them as 0/1 int32; the round trip is exact.
    """
    is_bool = jax.tree_util.tree_map(
        lambda x: jnp.result_type(x) == jnp.bool_, template)

    def enc(c):
        return jax.tree_util.tree_map(
            lambda b, x: x.astype(jnp.int32) if b else x, is_bool, c)

    def dec(c):
        return jax.tree_util.tree_map(lambda b, x: x != 0 if b else x,
                                      is_bool, c)

    return enc, dec


def solver_loop(cond: Callable[[Carry], Any], body: Callable[[Carry], Carry],
                carry0: Carry, *, bounded_steps: Optional[int] = None,
                checkpoint_every: Optional[int] = None) -> Carry:
    """while_loop, or its bounded reverse-differentiable substitute.

    bounded_steps=None  -> ``jax.lax.while_loop(cond, body, carry0)`` exactly.
    bounded_steps=K     -> ceil(K / checkpoint_every) scanned segments of
                           ``checkpoint_every`` unconditional body applications
                           (``cond`` is not consulted; at least K total).

    Contract on ``body`` (all engines in this repo satisfy it): an application
    on a carry whose lanes are all done must leave every observable output
    unchanged — then the bounded form is bitwise-equal to the while form
    whenever K covers the true iteration count, and K too small reproduces the
    max-iters outcome (lanes still marked not-done; engines report it as
    ``status == 1``).
    """
    if bounded_steps is None:
        enc, dec = bool_codec(carry0)
        out = jax.lax.while_loop(lambda c: cond(dec(c)),
                                 lambda c: enc(body(dec(c))), enc(carry0))
        return dec(out)
    bounded = int(bounded_steps)
    if bounded <= 0:
        raise ValueError(f"bounded_steps must be positive, got {bounded}")
    every = (default_checkpoint_every(bounded) if checkpoint_every is None
             else max(1, int(checkpoint_every)))
    every = min(every, bounded)
    n_seg = -(-bounded // every)

    @jax.checkpoint
    def segment(c):
        return jax.lax.fori_loop(0, every, lambda _i, cc: body(cc), c)

    out, _ = jax.lax.scan(lambda c, _: (segment(c), None), carry0, None,
                          length=n_seg)
    return out


def checkpointed_fori(lower: int, upper: int, body: Callable[[Any, Carry], Carry],
                      init: Carry, *,
                      checkpoint_every: Optional[int] = None) -> Carry:
    """``fori_loop(lower, upper, body, init)`` with periodic remat checkpoints.

    Runs the identical body sequence (same indices, same order), so the primal
    is bitwise-equal to the plain fori_loop; reverse-mode AD stores one carry
    per segment and recomputes inside segments.  Static bounds required.
    """
    lower, upper = int(lower), int(upper)
    n = upper - lower
    if n <= 0:
        return init
    every = (default_checkpoint_every(n) if checkpoint_every is None
             else max(1, int(checkpoint_every)))
    every = min(every, n)
    n_seg, rem = divmod(n, every)

    @jax.checkpoint
    def segment(c, start):
        return jax.lax.fori_loop(0, every,
                                 lambda j, cc: body(start + j, cc), c)

    if n_seg:
        starts = lower + every * jnp.arange(n_seg)
        init, _ = jax.lax.scan(lambda c, s: (segment(c, s), None), init,
                               starts)
    if rem:
        tail = jax.checkpoint(
            lambda c: jax.lax.fori_loop(upper - rem, upper, body, c))
        init = tail(init)
    return init
