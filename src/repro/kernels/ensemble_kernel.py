"""Generic fused-ensemble Pallas kernel factory (paper §5.2, all families).

One factory replaces the per-method kernels (the old tsit5-only
`build_ode_kernel` and the bespoke EM kernel): the TPU mapping —

  VREG lane <- 1 trajectory
  pallas grid over lane tiles (LANES); tiles retire independently
  loop-carried VMEM values (never HBM inside the integration)
  whole integration in one grid cell; one HBM flush at kernel end

— is method-independent, so it lives HERE exactly once: BlockSpec/grid
construction, trajectory-axis padding, output/stats assembly, and the
VMEM-budget-aware `lane_tile` selection (§5.2's occupancy formula).  What
varies per method family is only the *loop body*, supplied as a callback:

  body(ctx, u0 (n, B), p (m, B), extras) ->
      (us (S, n, B), u_final (n, B), t_final (B,), stats (7, B) int32)

with stats rows (naccept, nreject, status, nf, njac, nfact, steps_run) —
njac and nfact report the stiff family's Jacobian-evaluation and
W-factorization work (zero for erk/sde); steps_run is the tile's loop trip
count on every lane, the lane-steps the tile ran whether the lane was done
or not.  The body function's name becomes the kernel's (`ensemble_erk`,
`ensemble_rosenbrock`, `ensemble_sde`, `ensemble_sde_adaptive`), so a
device trace finds each family's kernel by name.  Bodies for the three
registered families (erk / rosenbrock / sde) are provided below; they reuse
the shared numerical engines (`core.solvers`, `core.rosenbrock`, `core.sde`)
unchanged — the paper's "automated translation": the same user RHS and the
same stepper run vmapped, lane-fused in XLA, and inside the device kernel.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.interp import kernel_lookups

Array = Any

# ---------------------------------------------------------------------------
# VMEM-aware lane-tile selection (paper §5.2 occupancy formula)
# ---------------------------------------------------------------------------

# 16 MiB: the scoped-VMEM limit Mosaic enforces on a v5e (its
# RESOURCE_EXHAUSTED error reports "limit 16.00M").  Budget half of it for
# the kernel's loop-carried state + output block, leaving headroom for
# pipelining/spills.  The per-lane formula below undercounts what Mosaic
# allocates by about 3x (docs/kernels.md): the small-n phases stay inside
# the limit because the 4096-lane cap binds first, but a tile that the
# budget itself limits can still exceed it.
VMEM_BYTES_PER_CORE = 16 * 1024 * 1024
DEFAULT_VMEM_BUDGET = VMEM_BYTES_PER_CORE // 2

# TPU vector-lane width: tiles should be multiples of this.
LANE_WIDTH = 128


def auto_lane_tile(n_state: int, n_param: int, n_save: int, *,
                   itemsize: int = 4, work_words: Optional[int] = None,
                   vmem_budget: Optional[int] = None,
                   max_tile: int = 4096, fixed_words: int = 0) -> int:
    """Largest 128-multiple tile whose per-lane VMEM footprint fits the budget.

    Per-lane bytes ≈ itemsize * (2*S*n  [output block + loop-carried copy]
                                 + work_words [state, stages, params, control]).
    `work_words` defaults to a generic ERK estimate; family-specific callers
    (Rosenbrock carries an n×n Jacobian per lane) pass their own.
    `fixed_words` is the tile-resident footprint SHARED by all lanes —
    broadcast dataset tables ("table" extras: one VMEM copy per grid cell,
    not per lane) — charged against the budget before the per-lane division
    so data-driven kernels don't over-subscribe VMEM.
    """
    if work_words is None:
        work_words = 12 * n_state + n_param + 16
    per_lane = itemsize * (2 * n_save * n_state + work_words)
    budget = DEFAULT_VMEM_BUDGET if vmem_budget is None else vmem_budget
    budget = max(0, budget - itemsize * fixed_words)
    tile = (budget // per_lane) // LANE_WIDTH * LANE_WIDTH
    return int(max(LANE_WIDTH, min(tile, max_tile)))


def lane_tile_ladder(n_state: int, n_param: int, n_save: int, *,
                     itemsize: int = 4, work_words: Optional[int] = None,
                     vmem_budget: Optional[int] = None, max_tile: int = 4096,
                     N: Optional[int] = None,
                     fixed_words: int = 0) -> Tuple[int, ...]:
    """Candidate lane tiles bracketing the §5.2 VMEM-optimal tile.

    The occupancy formula (`auto_lane_tile`) yields ONE tile; the real
    optimum depends on effects the formula cannot see (pipeline depth,
    spill behaviour, interpret-mode overhead), so the autotuner
    (`repro.core.autotune`) *times* a small ladder around it instead of
    trusting the formula blindly: {minimum LANE_WIDTH tile, half the
    formula's tile, the formula's tile, double it} — deduplicated, clamped
    to the padded ensemble width when `N` is given, sorted ascending.
    """
    auto = auto_lane_tile(n_state, n_param, n_save, itemsize=itemsize,
                          work_words=work_words, vmem_budget=vmem_budget,
                          max_tile=max_tile, fixed_words=fixed_words)
    half = max(LANE_WIDTH, (auto // 2) // LANE_WIDTH * LANE_WIDTH)
    cand = {LANE_WIDTH, half, auto, min(max_tile, 2 * auto)}
    if N is not None:
        cand = {padded_lane_width(N, t) for t in cand}
    return tuple(sorted(cand))


def erk_work_words(n_state: int, n_param: int, stages: int) -> int:
    return (stages + 4) * n_state + n_param + 16


def rosenbrock_work_words(n_state: int, n_param: int, stages: int = 2,
                          w_reuse: bool = False) -> int:
    # J and W are (n, n) PER LANE — the dominant term for stiff kernels —
    # plus one stage vector U_i per tableau stage (Rodas5P carries 8).
    # The lazy-W hot path (w_reuse) additionally CARRIES the Jacobian, the
    # factored W rows and the pivot/multiplier state across steps
    # (≈ 3·n² per lane in total); the §5.2 VMEM formula must know, or the
    # automatic lane_tile over-subscribes VMEM exactly when the stiff kernel
    # is at its most memory-hungry.
    nn = n_state * n_state
    return ((3 * nn + nn // 2 if w_reuse else 2 * nn)
            + (stages + 6) * n_state + n_param + 16)


def sde_work_words(n_state: int, n_param: int, m_noise: int) -> int:
    return 4 * n_state + m_noise + n_param + 8


# ---------------------------------------------------------------------------
# shared trajectory-axis padding / layout helpers (single home; the ops
# wrappers and the XLA lanes path all use these)
# ---------------------------------------------------------------------------

def padded_lane_width(N: int, lane_tile: int) -> int:
    """Vector width B actually run by `run_ensemble_kernel`.

    The tile is clamped to the ensemble size — but for ensembles LARGER than
    one `LANE_WIDTH`, rounded UP to a 128 multiple: TPU vector lanes come in
    128s, and the naive ``min(lane_tile, N)`` yields a ragged width whenever
    an explicit ``lane_tile > N`` is passed with ``N % 128 != 0`` (e.g.
    N=130, lane_tile=256 used to run a 130-wide kernel).  Ensembles with
    ``N <= LANE_WIDTH`` keep their exact width: Mosaic pads sub-128 widths
    internally on hardware, while the interpret/CPU test and benchmark paths
    pay real per-lane cost — rounding a 3-trajectory parity test up to 128
    lanes would be a 40x compute regression for zero hardware benefit.
    Explicit tiles smaller than the (rounded) ensemble size are honoured
    unchanged (tests drive 3-5-lane tiles through the interpreter)."""
    if N <= LANE_WIDTH:
        return int(max(1, min(lane_tile, N)))
    return int(max(1, min(lane_tile, -(-N // LANE_WIDTH) * LANE_WIDTH)))


def pad_lanes(x: Array, lane_tile: int) -> Tuple[Array, int]:
    """Pad the trailing (lane) axis to a multiple of `lane_tile` (edge mode
    keeps padded lanes numerically well-behaved). Returns (padded, orig_N)."""
    N = x.shape[-1]
    pad = (-N) % lane_tile
    if pad == 0:
        return x, N
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)], mode="edge"), N


def lanes_to_traj(us: Array, N: int) -> Array:
    """(..., LANES_padded) lane-major solution block -> (N, ...) trajectory-major."""
    return jnp.moveaxis(us, -1, 0)[:N]


class KernelContext(NamedTuple):
    """Static + grid information handed to the family loop body."""
    tile: Array        # pl.program_id(0) — this grid cell's tile index
    lane_tile: int     # B
    n_state: int
    n_param: int
    n_save: int


# extras are (kind, array) with kind:
#   "broadcast" — (K,) array identical for every tile (e.g. the saveat grid)
#   "lanes"     — (..., N) array tiled over the trajectory axis (noise tables);
#                 the body receives its VMEM ref, not a loaded value
#   "table"     — any-rank array identical for every tile (dataset table
#                 values: `prob.data` leaves).  Broadcast like "broadcast"
#                 but rank-preserving: the leaf rides its own BlockSpec into
#                 VMEM once per grid cell (the texture-memory economy) and
#                 the body sees it in its natural shape.  Convention: data
#                 leaves are always appended LAST in an extras list, so the
#                 family bodies can peel `extras[-n_leaves:]` off the tail.
Extra = Tuple[str, Array]

# rows of the int32 stats block every body returns (module docstring)
N_STATS = 7


def run_ensemble_kernel(body: Callable, u0s: Array, ps: Array, *, ts: Array,
                        extras: Sequence[Extra] = (),
                        lane_tile: Optional[int] = None,
                        work_words: Optional[int] = None,
                        vmem_budget: Optional[int] = None,
                        interpret: Optional[bool] = None,
                        fixed_words: int = 0):
    """Launch `body` over the ensemble and assemble an EnsembleResult.

    u0s (N, n), ps (N, m) trajectory-major; ts (S,) save-time grid for the
    result. All grid/BlockSpec plumbing, padding and stats assembly for every
    method family happens here — once.
    """
    from repro.core.ensemble import EnsembleResult

    N, n = u0s.shape
    m = ps.shape[1]
    S = int(ts.shape[0])
    dtype = u0s.dtype
    if lane_tile is None:
        lane_tile = auto_lane_tile(n, m, S, itemsize=dtype.itemsize,
                                   work_words=work_words,
                                   vmem_budget=vmem_budget,
                                   fixed_words=fixed_words)
    # clamp to the ensemble size (no point padding a small ensemble up to the
    # VMEM-optimal tile); large ragged ensembles round up to a LANE_WIDTH
    # multiple.  The XLA lanes path (`core.ensemble._tile_lanes`) derives its
    # width from the SAME helper: XLA codegen is width-sensitive at the ulp
    # level, so equal widths are what keep the two backends bitwise-comparable
    B = padded_lane_width(N, lane_tile)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    u0_l, _ = pad_lanes(u0s.T, B)
    p_l, _ = pad_lanes(ps.T, B)
    Np = u0_l.shape[-1]
    T = Np // B

    in_specs = [pl.BlockSpec((n, B), lambda i: (0, i)),
                pl.BlockSpec((m, B), lambda i: (0, i))]
    args = [u0_l, p_l]
    unwrap = []  # how the kernel recovers each extra's natural shape
    for kind, arr in extras:
        if kind == "broadcast":
            args.append(jnp.asarray(arr)[None, :])
            K = args[-1].shape[1]
            in_specs.append(pl.BlockSpec((1, K), lambda i: (0, 0)))
            unwrap.append(lambda v: v[0])
        elif kind == "lanes":
            # handed to the body as the VMEM ref itself: the body reads rows
            # at dynamic indices (ref[k]), which Mosaic lowers and a value
            # dynamic_slice it does not
            padded, _ = pad_lanes(jnp.asarray(arr), B)
            args.append(padded)
            blk = padded.shape[:-1] + (B,)
            nd = padded.ndim
            in_specs.append(pl.BlockSpec(
                blk, lambda i, _nd=nd: (0,) * (_nd - 1) + (i,)))
            unwrap.append(None)
        elif kind == "table":
            # dataset leaf: flatten to one VMEM row broadcast to every grid
            # cell, restore the natural shape inside the kernel
            a = jnp.asarray(arr)
            sh = a.shape
            flat = a.reshape(1, -1)
            K = flat.shape[1]
            args.append(flat)
            in_specs.append(pl.BlockSpec((1, K), lambda i: (0, 0)))
            unwrap.append(lambda v, _sh=sh: v.reshape(_sh))
        else:
            raise ValueError(f"unknown extra kind {kind!r}")

    out_shape = [
        jax.ShapeDtypeStruct((S, n, Np), dtype),      # us
        jax.ShapeDtypeStruct((n, Np), dtype),         # u_final
        jax.ShapeDtypeStruct((1, Np), dtype),         # t_final
        # naccept / nreject / status / nf / njac / nfact / steps_run
        jax.ShapeDtypeStruct((N_STATS, Np), jnp.int32),
    ]
    out_specs = [
        pl.BlockSpec((S, n, B), lambda i: (0, 0, i)),
        pl.BlockSpec((n, B), lambda i: (0, i)),
        pl.BlockSpec((1, B), lambda i: (0, i)),
        pl.BlockSpec((N_STATS, B), lambda i: (0, i)),
    ]

    n_in = len(args)

    def kernel(*refs):
        u0 = refs[0][...]
        p = refs[1][...]
        ex = tuple(r if fn is None else fn(r[...])
                   for fn, r in zip(unwrap, refs[2:n_in]))
        us_ref, uf_ref, tfin_ref, stats_ref = refs[n_in:]
        ctx = KernelContext(tile=pl.program_id(0), lane_tile=B, n_state=n,
                            n_param=m, n_save=S)
        with kernel_lookups():
            us, uf, t_final, stats = body(ctx, u0, p, ex)
        us_ref[...] = us                  # (S, n, B): one HBM flush
        uf_ref[...] = uf
        tfin_ref[...] = t_final[None]
        stats_ref[...] = stats.astype(jnp.int32)

    fn = pl.pallas_call(kernel, grid=(T,), in_specs=in_specs,
                        out_specs=out_specs, out_shape=out_shape,
                        interpret=interpret, name=body.__name__)
    us, uf, t_fin, stats = fn(*args)
    return EnsembleResult(
        ts=jnp.asarray(ts, dtype), us=lanes_to_traj(us, N),
        u_final=uf.T[:N], t_final=t_fin[0, :N],
        naccept=stats[0, :N], nreject=stats[1, :N],
        nf=jnp.sum(stats[3, :N]), status=jnp.max(stats[2, :N]),
        njac=jnp.sum(stats[4, :N]), nfact=jnp.sum(stats[5, :N]),
        steps_run=stats[6, :N])


def kernel_adjoint(primal_fn: Callable, replay_fn: Callable) -> Callable:
    """Reverse-mode AD across the Pallas kernel boundary.

    ``pallas_call`` has no transpose rule, so the fused kernels cannot be
    vjp'd directly.  This factory keeps the FORWARD solve on the kernel
    (``primal_fn``) and installs a `jax.custom_vjp` whose backward pass
    re-runs the kernel's XLA twin (``replay_fn`` — the bounded, checkpointed
    `repro.core.loops.solver_loop` path of the same family) under `jax.vjp`.
    The forward pass stores only the (u0s, ps) residuals; the replay's
    checkpointed segments bound the reverse-pass memory (periodic carry
    checkpoints — u, t, dt, RNG counters, J/LU freshness — with recompute
    inside segments), so peak memory stays O(sqrt-steps), never O(steps).
    SDE replays are exact: the counter-RNG noise is a pure function of
    (seed; step/grid index, row, global lane), so the recomputed path is the
    path the kernel integrated, bitwise.

    Both callables map ``(u0s, ps, *extra) -> EnsembleResult``; the variadic
    tail exists for data-driven problems, whose dataset leaves must be REAL
    custom_vjp arguments (a custom_vjp closure must not capture tracers — the
    way a bound closure would under `jax.grad` of table values), so gradients
    flow to the tables too: calibrating a forcing curve from data is just
    `jax.grad` over the leaf arguments.  Gradients flow through the
    continuous state outputs ``us`` and ``u_final``; solver statistics,
    snapshot times and event locations are non-differentiable outputs (their
    cotangents are dropped).
    """

    @jax.custom_vjp
    def run(u0s, ps, *extra):
        return primal_fn(u0s, ps, *extra)

    def fwd(u0s, ps, *extra):
        return primal_fn(u0s, ps, *extra), (u0s, ps, extra)

    def bwd(residuals, ct):
        u0s, ps, extra = residuals

        def states(u, p, *ex):
            res = replay_fn(u, p, *ex)
            return res.us, res.u_final

        _, vjp = jax.vjp(states, u0s, ps, *extra)
        return vjp((ct.us, ct.u_final))

    run.defvjp(fwd, bwd)
    return run


# ---------------------------------------------------------------------------
# double-buffered HBM<->VMEM save staging (large save grids / large n)
# ---------------------------------------------------------------------------

def save_chunk_count(n_state: int, n_param: int, n_save: int, *,
                     itemsize: int = 4, work_words: Optional[int] = None,
                     vmem_budget: Optional[int] = None,
                     fixed_words: int = 0) -> int:
    """How many saveat segments the staged driver needs (1 = no staging).

    `run_ensemble_kernel` keeps the whole (S, n, B) output block VMEM-resident
    for the kernel's lifetime; when S·n is large the §5.2 formula can only
    shrink the tile down to its LANE_WIDTH floor, and past that the footprint
    simply does not fit the budget.  This computes, at that minimum tile, the
    number of saves one segment can afford, and hence the segment count
    `run_ensemble_kernel_staged` should split the grid into.
    """
    if work_words is None:
        work_words = 12 * n_state + n_param + 16
    budget = DEFAULT_VMEM_BUDGET if vmem_budget is None else vmem_budget
    # broadcast tables are tile-resident in every segment — same charge as
    # auto_lane_tile, or staging re-over-subscribes exactly what it fixes
    budget = max(0, budget - itemsize * fixed_words)
    per_lane_words = budget // (LANE_WIDTH * itemsize)
    max_saves = (per_lane_words - work_words) // (2 * n_state)
    if max_saves >= n_save:
        return 1
    return int(-(-n_save // max(1, max_saves)))


def run_ensemble_kernel_staged(body_factory: Callable, u0s: Array, ps: Array,
                               *, ts: Array, save_chunks: int,
                               lane_tile: Optional[int] = None,
                               work_words: Optional[int] = None,
                               vmem_budget: Optional[int] = None,
                               interpret: Optional[bool] = None,
                               fixed_words: int = 0):
    """Segmented launch: double-buffer the save block between HBM and VMEM.

    The save grid `ts` (concrete, ascending, all > t0) is split into
    `save_chunks` segments; each segment runs ONE `run_ensemble_kernel`
    launch whose (S_seg, n, B) output block fits the VMEM budget, flushing to
    HBM at segment end while the next launch re-stages only the (n, B) final
    state — the classic two-buffers-in-flight staging pattern at saveat
    granularity, which is the coarsest (and therefore cheapest) place to cut.
    `u_final`/`t_final` and the step counters thread between segments at the
    JAX level; `body_factory(t_start, seg_ts, last)` builds each segment's
    loop body + extras (the erk wrapper `repro.kernels.tsit5.ops` supplies
    one that restarts integration at the previous segment's endpoint).

    Numerics: fixed-dt runs whose segment boundaries land on the step grid
    are bitwise-identical to the unstaged kernel; adaptive runs restart the
    controller (dt0, PI history) at each boundary, so they agree to solver
    accuracy, not bitwise (see docs/kernels.md).
    """
    from repro.core.ensemble import EnsembleResult

    ts_np = np.asarray(ts)
    S = int(ts_np.shape[0])
    save_chunks = int(max(1, min(save_chunks, S)))
    segs = [idx for idx in np.array_split(np.arange(S), save_chunks)
            if idx.size]

    u_cur = u0s
    parts, acc = [], None
    for k, idx in enumerate(segs):
        seg_ts = ts_np[idx]
        t_start = float(ts_np[idx[0] - 1]) if k else None  # None: problem t0
        body, extras = body_factory(t_start, seg_ts, k == len(segs) - 1)
        res = run_ensemble_kernel(
            body, u_cur, ps, ts=jnp.asarray(seg_ts, u0s.dtype),
            extras=extras, lane_tile=lane_tile, work_words=work_words,
            vmem_budget=vmem_budget, interpret=interpret,
            fixed_words=fixed_words)
        u_cur = res.u_final
        parts.append(res.us)
        if acc is None:
            acc = res
        else:
            acc = acc._replace(
                u_final=res.u_final, t_final=res.t_final,
                naccept=acc.naccept + res.naccept,
                nreject=acc.nreject + res.nreject,
                nf=acc.nf + res.nf, njac=acc.njac + res.njac,
                nfact=acc.nfact + res.nfact,
                steps_run=acc.steps_run + res.steps_run,
                status=jnp.maximum(acc.status, res.status))
    return acc._replace(ts=jnp.asarray(ts_np, u0s.dtype),
                        us=jnp.concatenate(parts, axis=1))


# ---------------------------------------------------------------------------
# family loop bodies — each is the shared numerical engine in lanes mode,
# specialized (closure/JIT) on the problem, exactly as the paper's kernel
# generator compiles the problem definition into the device kernel.
# ---------------------------------------------------------------------------

def _data_binder(data):
    """Plumbing for data-driven problems inside kernel bodies.

    `data` is the problem's dataset pytree, used as a TEMPLATE (treedef +
    leaf count) only: the actual table values arrive as the trailing "table"
    extras (the extras-last convention above), so they are real kernel
    arguments — VMEM-resident, and differentiable through `kernel_adjoint`'s
    variadic tail.  Returns `rebind(extras) -> (core_extras, d)` peeling the
    leaf tail off and rebuilding the dataset pytree, or None without data.
    """
    if data is None:
        return None
    leaves, treedef = jax.tree_util.tree_flatten(data)
    k = len(leaves)

    def rebind(extras):
        split = len(extras) - k
        d = jax.tree_util.tree_unflatten(treedef, list(extras[split:]))
        return extras[:split], d

    return rebind


def _per_lane(iters, like):
    """The tile's loop trip count (an int32 scalar) on every lane."""
    return jnp.broadcast_to(iters, like.shape).astype(jnp.int32)


def erk_body(f, tab, *, t0: float, tf: float, dt0: float, rtol: float,
             atol: float, adaptive: bool, max_iters: int, event=None,
             data=None):
    """Adaptive embedded-RK integration; extras[0] = saveat grid (S,);
    data-driven problems append their table leaves last (see _data_binder)."""
    from repro.core.solvers import AdaptiveOptions, solve_adaptive

    rebind = _data_binder(data)

    def ensemble_erk(ctx, u0, p, extras):
        fb = f
        if rebind is not None:
            extras, d = rebind(extras)
            fb = lambda u_, p_, t_: f(u_, p_, t_, d)
        saveat_v = extras[0]
        opts = AdaptiveOptions(rtol=rtol, atol=atol, max_iters=max_iters,
                               adaptive=adaptive)
        res = solve_adaptive(fb, tab, u0, p, t0, tf, dt0, saveat=saveat_v,
                             opts=opts, event=event, lanes=True)
        if event is not None:
            res, _ = res
        zero = jnp.zeros_like(res.naccept)
        stats = jnp.stack([res.naccept, res.nreject,
                           res.status * jnp.ones_like(res.naccept), res.nf,
                           zero, zero, _per_lane(res.iters, res.naccept)])
        return res.us, res.u_final, res.t_final, stats

    return ensemble_erk


def rosenbrock_body(f, rtab, *, jac=None, t0: float, tf: float, dt0: float,
                    rtol: float, atol: float, max_iters: int, event=None,
                    w_reuse=None, data=None):
    """s-stage Rosenbrock stiff integration (any `RosenbrockTableau`:
    Rosenbrock23 / Rodas4 / Rodas5P) with the batched-LU W-solves *inlined*
    (linsolve="lanes": paper §5.1.3 inside the fused kernel, lanes-wide
    partial pivoting).  `jac` is the analytic-Jacobian hook (None: jacfwd
    inside the kernel).  `w_reuse` enables the lazy-W hot path: the Jacobian,
    the factored LU(W) (rows/swaps/multipliers of the lanes LU) and the dt it
    was factored at ride the while_loop carry in VMEM, refreshed per lane
    only when the `WReusePolicy` freshness controller asks — the fused
    kernel's dominant per-step cost (jacfwd + O(n³) elimination) is then paid
    only on refresh steps.  Events run the shared per-lane machinery
    (`repro.core.events`) inside the fused loop.  extras[0] = saveat grid
    (S,); data-driven problems append their table leaves last."""
    from repro.core.rosenbrock import solve_rosenbrock

    rebind = _data_binder(data)

    def ensemble_rosenbrock(ctx, u0, p, extras):
        fb, jb = f, jac
        if rebind is not None:
            extras, d = rebind(extras)
            fb = lambda u_, p_, t_: f(u_, p_, t_, d)
            if jac is not None:
                jb = lambda u_, p_, t_: jac(u_, p_, t_, d)
        saveat_v = extras[0]
        res = solve_rosenbrock(fb, rtab, u0, p, t0, tf, dt0, rtol=rtol,
                               atol=atol, saveat=saveat_v,
                               max_iters=max_iters, lanes=True,
                               linsolve="lanes", jac=jb, event=event,
                               w_reuse=w_reuse)
        if event is not None:
            res, _ = res
        stats = jnp.stack([res.naccept, res.nreject, res.status, res.nf,
                           jnp.broadcast_to(res.njac, res.naccept.shape),
                           jnp.broadcast_to(res.nfact, res.naccept.shape),
                           _per_lane(res.iters, res.naccept)])
        return res.us, res.u_final, res.t_final, stats

    return ensemble_rosenbrock


def sde_body(f, g, stepper, noise: str, *, t0: float, dt: float,
             n_steps: int, save_every: int, m_noise: int, seed: int,
             use_table: bool, nf_per_step: int = 1, event=None, data=None):
    """Fixed-dt SDE integration with in-kernel counter RNG (threefry keyed by
    (seed; step, noise-row, GLOBAL lane) — replayable, no noise storage), or a
    pre-drawn table via extras[1] ("lanes" kind, (n_steps, m, N)).

    extras[0] ("broadcast", (1,)) is the shard's global lane offset;
    data-driven problems append their dataset table leaves LAST (after the
    optional noise table — the extras-last convention).  Events run the
    shared per-lane machinery (`repro.core.events`) inside the fused loop,
    with termination masks freezing finished lanes."""
    from repro.core.sde import (sde_event_state0, sde_step_and_save,
                                sde_step_save_event)
    from repro.kernels.rng import counter_normals_threefry

    S = n_steps // save_every
    rebind = _data_binder(data)

    def ensemble_sde(ctx, u0, p, extras):
        f_, g_ = f, g
        if rebind is not None:
            extras, d = rebind(extras)
            f_ = lambda u_, p_, t_: f(u_, p_, t_, d)
            g_ = lambda u_, p_, t_: g(u_, p_, t_, d)
        B = ctx.lane_tile
        dtype = u0.dtype
        offset = jnp.asarray(extras[0], jnp.uint32)[0]
        lane = (offset + jnp.uint32(ctx.tile) * jnp.uint32(B)
                + jax.lax.broadcasted_iota(jnp.uint32, (m_noise, B), 1))
        rows = jax.lax.broadcasted_iota(jnp.uint32, (m_noise, B), 0)
        table = extras[1] if use_table else None

        def noise_fn(k):
            if use_table:
                return table[k].astype(dtype)   # dynamic row of the VMEM ref
            return counter_normals_threefry(seed, k, lane, rows, dtype)

        us0 = jnp.zeros((S, ctx.n_state, B), dtype)
        i32 = lambda v: jnp.full((B,), v, jnp.int32)
        if event is None:
            def step(k, carry):
                u, us = carry
                return sde_step_and_save(stepper, f_, g_, noise, u, us, p, t0,
                                         dt, k, noise_fn(k), save_every,
                                         select=True)

            u_f, us = jax.lax.fori_loop(0, n_steps, step, (u0, us0))
            t_final = jnp.full((B,), t0 + n_steps * dt, dtype)
            naccept = i32(n_steps)
        else:
            def step(k, carry):
                u, us, estate = carry
                return sde_step_save_event(stepper, f_, g_, noise, event, u,
                                           us, estate, p, t0, dt, k,
                                           noise_fn(k), save_every,
                                           select=True)

            estate0 = sde_event_state0((B,), t0, dtype)
            u_f, us, estate = jax.lax.fori_loop(0, n_steps, step,
                                                (u0, us0, estate0))
            t_final = estate["t_out"].astype(dtype)
            naccept = estate["naccept"]
        stats = jnp.stack([naccept, i32(0), i32(0),
                           i32(n_steps * nf_per_step), i32(0), i32(0),
                           i32(n_steps)])
        return us, u_f, t_final, stats

    return ensemble_sde


def sde_adaptive_body(f, g, stepper, noise: str, *, t0: float, tf: float,
                      dt0: float, rtol: float, atol: float, max_iters: int,
                      m_noise: int, seed: int, depth: int, order: float,
                      nf_per_step: int, event=None, error_est: str = "doubling",
                      embedded=None, est_order=None, nf_per_attempt=None,
                      data=None):
    """Adaptive SDE integration fused into the kernel: embedded-pair or
    step-doubling error control with virtual-Brownian-tree noise
    (rejection-safe: the SAME (seed; lane, row, dyadic-time) stream on every
    strategy/backend — see `repro.core.sde.sde_solve_adaptive`, which this
    body wraps unchanged, so estimator choice cannot split the backends).
    extras[0] = saveat grid (S,), extras[1] = ("broadcast", (1,)) global lane
    offset; data-driven problems append their table leaves last."""
    from repro.core.sde import sde_solve_adaptive

    rebind = _data_binder(data)

    def ensemble_sde_adaptive(ctx, u0, p, extras):
        f_, g_ = f, g
        if rebind is not None:
            extras, d = rebind(extras)
            f_ = lambda u_, p_, t_: f(u_, p_, t_, d)
            g_ = lambda u_, p_, t_: g(u_, p_, t_, d)
        B = ctx.lane_tile
        saveat_v = extras[0]
        offset = jnp.asarray(extras[1], jnp.uint32)[0]
        lane = (offset + jnp.uint32(ctx.tile) * jnp.uint32(B)
                + jax.lax.broadcasted_iota(jnp.uint32, (B,), 0))
        res = sde_solve_adaptive(f_, g_, stepper, noise, u0, p, t0, tf, dt0,
                                 seed=seed, lane_idx=lane, m_noise=m_noise,
                                 saveat=saveat_v, rtol=rtol, atol=atol,
                                 max_iters=max_iters, event=event, lanes=True,
                                 depth=depth, order=order,
                                 nf_per_step=nf_per_step, error_est=error_est,
                                 embedded=embedded, est_order=est_order,
                                 nf_per_attempt=nf_per_attempt)
        if event is not None:
            res, _ = res
        zero = jnp.zeros_like(res.naccept)
        stats = jnp.stack([res.naccept, res.nreject,
                           res.status * jnp.ones_like(res.naccept), res.nf,
                           zero, zero, _per_lane(res.iters, res.naccept)])
        return res.us, res.u_final, res.t_final, stats

    return ensemble_sde_adaptive
