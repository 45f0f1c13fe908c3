"""Counting the vector operations of a plain `jax.numpy` step.

The configurations state their work per lane-step as sums of commented
terms.  This module counts the same work a second way, from the jaxpr of a
plain one-lane step written in the configuration module from the
equations and the tableau, so the two can be checked against each other
(tests/bench/test_bench_workcount.py).  The rule, for both: every element
an arithmetic, comparison, select, bit or conversion primitive produces is
one operation, a transcendental included; a sum over k elements is k - 1
additions; moving data (broadcast, reshape, slice, stack) is free.
"""
from __future__ import annotations

import math

import jax
from jax.extend import core as jcore

ELEMENTWISE = frozenset({
    "add", "sub", "mul", "div", "neg", "max", "min", "abs", "sign",
    "sqrt", "rsqrt", "exp", "log", "pow", "integer_pow", "sin", "cos",
    "tan", "tanh", "logistic", "log1p", "expm1",
    "eq", "ne", "lt", "le", "gt", "ge", "select_n", "clamp",
    "and", "or", "xor", "not", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "convert_element_type", "is_finite",
})
FREE = frozenset({
    "broadcast_in_dim", "reshape", "squeeze", "expand_dims", "concatenate",
    "slice", "dynamic_slice", "copy", "copy_p", "iota", "transpose",
    "pjit", "jit", "closed_call", "custom_jvp_call", "custom_vjp_call",
    "stop_gradient",
})
REDUCTIONS = frozenset({"reduce_sum", "reduce_max", "reduce_min"})


def _size(aval) -> int:
    return int(math.prod(getattr(aval, "shape", ())))


def count_jaxpr(jaxpr) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        subs = [v for v in eqn.params.values()
                if isinstance(v, (jcore.Jaxpr, jcore.ClosedJaxpr))]
        if subs:
            for s in subs:
                total += count_jaxpr(getattr(s, "jaxpr", s))
            continue
        if name in ELEMENTWISE:
            total += sum(_size(v.aval) for v in eqn.outvars)
        elif name in REDUCTIONS:
            total += _size(eqn.invars[0].aval) - _size(eqn.outvars[0].aval)
        elif name not in FREE:
            raise ValueError(f"no counting rule for primitive {name!r}")
    return total


def count_ops(fn, *args) -> int:
    """Vector operations in one call of `fn(*args)`, by the rule above."""
    return count_jaxpr(jax.make_jaxpr(fn)(*args).jaxpr)
