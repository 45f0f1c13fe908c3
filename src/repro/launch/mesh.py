"""Production mesh construction.

IMPORTANT: functions only — importing this module must never touch jax device
state (the dry-run sets XLA_FLAGS before any jax import; everything else sees
the real single device).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 ("data","model") single-pod (256 chips) or 2x16x16
    ("pod","data","model") multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh():
    """Whatever devices exist, as a 1D 'data' mesh (CPU tests/examples).

    Auto axis type: the ensemble path shards through `jax.shard_map`, and
    arrays typed with an Explicit mesh axis (`jax.make_mesh`'s default)
    cannot then be moved to or compared against a single device."""
    n = len(jax.devices())
    return jax.make_mesh((n,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
