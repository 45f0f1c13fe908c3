"""Dataset interpolation — the TPU adaptation of the paper's texture memory (§6.7).

GPUs give hardware linear interpolation + boundary handling on uniform grids via
texture units. TPUs have no texture hardware; the native equivalents are:

  mode="gather"  — index computation + jnp.take (general; XLA gather).
                   Inside a Pallas kernel body, where Mosaic lowers no 1-D
                   gather, and in the kernel family's XLA twin, a 1-D
                   lookup becomes a masked sum over the K nodes instead
                   (`kernel_lookups`; bitwise the same value).
  mode="onehot"  — interpolation weights as a (…, K) one-hot-pair matrix
                   contracted with the table: a matmul, i.e. MXU work. Inside a
                   Pallas kernel the table is VMEM-resident (BlockSpec broadcast
                   to every trajectory tile), so a lookup costs one small matmul
                   and zero HBM traffic — the same "single memory read" economy
                   texture memory buys on NVIDIA.
  mode="cubic"   — Catmull–Rom cubic convolution (Keys a = -1/2): the OTHER
                   texture-unit operation (CUDA's tex1D cubic filtering is
                   built from linear fetches the same way).  Four-point gather
                   per query, C1-continuous, reproduces polynomials up to
                   degree 2 exactly (third-order accurate).

All modes clamp out-of-range queries to the boundary (texture
address-mode=clamp) and require uniformly spaced data, exactly like the paper.

Tables are registered JAX pytrees whose only leaf is ``values`` (``x0``/``dx``
ride the treedef as static metadata).  That single fact is what lets a
``prob.data`` pytree of tables be traced by `jax.grad` (calibrating a forcing
curve from data), broadcast — not sharded — through `shard_map`, and passed
into the fused Pallas kernels as real BlockSpec arguments
(`repro.kernels.ensemble_kernel`, extra kind "table").
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp

Array = Any

MODES = ("gather", "onehot", "cubic")


@dataclasses.dataclass(frozen=True)
class UniformTable1D:
    """values[i] sampled at x0 + i*dx, i in [0, K)."""
    values: Array   # (K,)
    x0: float
    dx: float

    @property
    def K(self) -> int:
        return self.values.shape[0]


@dataclasses.dataclass(frozen=True)
class UniformTable2D:
    """values[i, j] sampled at (x0 + i*dx, y0 + j*dy)."""
    values: Array   # (Kx, Ky)
    x0: float
    dx: float
    y0: float
    dy: float


# Tables are pytrees: `values` is the (traceable, differentiable) leaf; the
# grid origin/spacing are static aux data.  This is the contract the whole
# data-driven-RHS capability rests on — see the module docstring.
jax.tree_util.register_pytree_node(
    UniformTable1D,
    lambda t: ((t.values,), (t.x0, t.dx)),
    lambda aux, ch: UniformTable1D(ch[0], *aux))
jax.tree_util.register_pytree_node(
    UniformTable2D,
    lambda t: ((t.values,), (t.x0, t.dx, t.y0, t.dy)),
    lambda aux, ch: UniformTable2D(ch[0], *aux))


def _locate(x, x0, dx, K):
    """Clamped cell index + fractional offset."""
    s = (x - x0) / dx
    s = jnp.clip(s, 0.0, float(K - 1))
    i = jnp.clip(jnp.floor(s).astype(jnp.int32), 0, K - 2)
    w = s - i  # in [0, 1]; w == 1 exactly at the last node
    return i, w


def _catmull_rom_weights(w):
    """Keys cubic-convolution weights (a = -1/2) for nodes i-1, i, i+1, i+2."""
    w2 = w * w
    w3 = w2 * w
    return (0.5 * (-w3 + 2.0 * w2 - w),
            0.5 * (3.0 * w3 - 5.0 * w2 + 2.0),
            0.5 * (-3.0 * w3 + 4.0 * w2 + w),
            0.5 * (w3 - w2))


_IN_KERNEL = contextvars.ContextVar("repro_interp_in_kernel", default=False)


@contextlib.contextmanager
def kernel_lookups(enabled: bool = True):
    """Trace 1-D table lookups as masked sums while a Pallas kernel body
    (or the kernel family's XLA twin) is being traced.  A jitted callback
    first traced outside this context keeps its gather inside it."""
    token = _IN_KERNEL.set(bool(enabled))
    try:
        yield
    finally:
        _IN_KERNEL.reset(token)


def _take1d(values, i):
    """values[i] for a (K,) table and integer indices i of any shape.

    An XLA gather, except under `kernel_lookups`: the TPU kernel compiler
    (Mosaic) lowers no 1-D gather, and the fused kernels hold the table in
    VMEM, so there the lookup is a masked sum over the K nodes.  Exactly
    one term of the sum is nonzero, so both equal ``jnp.take(values, i)``
    bitwise; the sum costs O(K) per lookup, which is why it stays with the
    kernel family."""
    if not _IN_KERNEL.get():
        return jnp.take(values, i)
    K = values.shape[0]
    node = jax.lax.broadcasted_iota(jnp.int32, (K,) + jnp.shape(i), 0)
    vals = values.reshape((K,) + (1,) * jnp.ndim(i))
    return jnp.sum(jnp.where(node == i, vals, jnp.zeros((), values.dtype)),
                   axis=0)


def interp1d(table: UniformTable1D, x, mode: str = "gather"):
    """Interpolation at x (any shape). Clamped boundaries, all modes."""
    K = table.K
    i, w = _locate(x, table.x0, table.dx, K)
    if mode == "gather":
        v0 = _take1d(table.values, i)
        v1 = _take1d(table.values, i + 1)
        return v0 * (1.0 - w) + v1 * w
    if mode == "onehot":
        # weights (…, K): (1-w) at i, w at i+1 — contraction is a matmul (MXU)
        iota = jnp.arange(K, dtype=jnp.int32)
        xsh = jnp.shape(x)
        ii = i.reshape(xsh + (1,))
        ww = w.reshape(xsh + (1,))
        wmat = (jnp.where(iota == ii, 1.0 - ww, 0.0)
                + jnp.where(iota == ii + 1, ww, 0.0))
        return wmat @ table.values
    if mode == "cubic":
        # Catmull–Rom over the 4-point stencil {i-1, i, i+1, i+2}; stencil
        # indices clamp to [0, K-1] — node replication at the edges, the same
        # address-mode=clamp semantics as the linear modes (queries outside
        # the grid keep returning the boundary value exactly: w there is 0/1
        # and the replicated stencil collapses the cubic onto that node).
        ws = _catmull_rom_weights(w)
        out = None
        for off, wk in zip((-1, 0, 1, 2), ws):
            idx = jnp.clip(i + off, 0, K - 1)
            term = wk * _take1d(table.values, idx)
            out = term if out is None else out + term
        return out
    raise ValueError(f"unknown mode {mode!r} (one of {MODES})")


def interp2d(table: UniformTable2D, x, y, mode: str = "gather"):
    """Bilinear/bicubic interpolation at (x, y) (broadcast shapes). Clamped."""
    Kx, Ky = table.values.shape
    i, wx = _locate(x, table.x0, table.dx, Kx)
    j, wy = _locate(y, table.y0, table.dy, Ky)
    if mode == "gather":
        flat = table.values.reshape(-1)
        idx = i * Ky + j
        v00 = jnp.take(flat, idx)
        v01 = jnp.take(flat, idx + 1)
        v10 = jnp.take(flat, idx + Ky)
        v11 = jnp.take(flat, idx + Ky + 1)
        return (v00 * (1 - wx) * (1 - wy) + v01 * (1 - wx) * wy
                + v10 * wx * (1 - wy) + v11 * wx * wy)
    if mode == "onehot":
        # separable one-hot pair per axis; two small matmuls
        ix = jnp.arange(Kx, dtype=jnp.int32)
        iy = jnp.arange(Ky, dtype=jnp.int32)
        xsh = jnp.shape(x)
        ie = i.reshape(xsh + (1,))
        je = j.reshape(xsh + (1,))
        wxe = wx.reshape(xsh + (1,))
        wye = wy.reshape(xsh + (1,))
        wmx = (jnp.where(ix == ie, 1.0 - wxe, 0.0)
               + jnp.where(ix == ie + 1, wxe, 0.0))         # (…, Kx)
        wmy = (jnp.where(iy == je, 1.0 - wye, 0.0)
               + jnp.where(iy == je + 1, wye, 0.0))         # (…, Ky)
        rows = wmx @ table.values                            # (…, Ky)
        return jnp.sum(rows * wmy, axis=-1)
    if mode == "cubic":
        # separable Catmull–Rom: 4x4 clamped stencil, tensor-product weights
        flat = table.values.reshape(-1)
        wxs = _catmull_rom_weights(wx)
        wys = _catmull_rom_weights(wy)
        out = None
        for ox, wkx in zip((-1, 0, 1, 2), wxs):
            ii = jnp.clip(i + ox, 0, Kx - 1)
            for oy, wky in zip((-1, 0, 1, 2), wys):
                jj = jnp.clip(j + oy, 0, Ky - 1)
                term = wkx * wky * jnp.take(flat, ii * Ky + jj)
                out = term if out is None else out + term
        return out
    raise ValueError(f"unknown mode {mode!r} (one of {MODES})")


# ---------------------------------------------------------------------------
# `prob.data` pytree helpers — the dispatch layers (ensemble/api/autotune/
# kernel factory) handle data through these three functions only.
# ---------------------------------------------------------------------------

def data_flatten(data) -> Tuple[list, Any]:
    """(leaves, treedef) of a `prob.data` pytree — leaves are the table value
    arrays (tables are registered pytree nodes), in deterministic order."""
    return jax.tree_util.tree_flatten(data)


def data_unflatten(treedef, leaves):
    return jax.tree_util.tree_unflatten(treedef, list(leaves))


def data_words(data) -> int:
    """Total elements across all table leaves — the VMEM footprint (in words)
    a broadcast-resident copy of the dataset costs each lane tile.  Charged
    as `fixed_words` against the §5.2 budget by the kernel factory."""
    if data is None:
        return 0
    return int(sum(int(jnp.size(leaf))
                   for leaf in jax.tree_util.tree_leaves(data)))


def data_signature(data) -> str:
    """Compact shape/dtype signature of a data pytree — the autotune
    configuration-key component ("none" without data): different table
    geometries cost differently in the kernels, so they must not share a
    profile-cache entry."""
    if data is None:
        return "none"
    leaves = jax.tree_util.tree_leaves(data)
    if not leaves:
        return "empty"
    return "+".join(
        "x".join(str(int(s)) for s in jnp.shape(leaf))
        + jnp.dtype(jnp.result_type(leaf)).name
        for leaf in leaves)
