"""Distributed ensemble solving — the paper's MPI composition (§6.3) on a mesh.

The trajectory axis is embarrassingly parallel: `shard_map` splits the ensemble
over the ("pod", "data") mesh axes, each shard runs the fused local solve
(zero collectives inside — same property the paper's CUDA-aware-MPI demo
exploits), and only moment reductions (`ensemble_moments`) communicate, via
psum. On the 2×16×16 production mesh this is 512-way trajectory parallelism;
the 2^30-trajectory configuration of §6.3 is exercised by the dry-run.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .ensemble import EnsembleResult, solve_ensemble_local
from .interp import data_flatten, data_unflatten
from .problem import EnsembleProblem

Array = Any


def _ensemble_axes(mesh: Mesh, shard_axes: Optional[Sequence[str]]):
    if shard_axes is None:
        shard_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return tuple(shard_axes)


def solve_ensemble(eprob: EnsembleProblem, mesh: Optional[Mesh] = None,
                   shard_axes: Optional[Sequence[str]] = None,
                   **kw) -> EnsembleResult:
    """Solve an ensemble, optionally sharded over `mesh`.

    This is the distributed face of the unified front door: `alg=` may be any
    registered method (erk / rosenbrock / sde — see `repro.core.methods`),
    dispatched through any `ensemble=`/`backend=` combination by
    `solve_ensemble_local`. Trajectories are split over `shard_axes` (default:
    every ensemble-capable axis present — "pod" and "data"); each device runs
    the fused kernel path on its local chunk. N must divide by the total shard
    count.

    SDE counter-RNG streams are GLOBAL: each shard's `lane_offset` (its first
    trajectory's global index) is threaded into the local solve, so shard k
    draws the (seed; step, row, k*n_local + i) stream — sharded and local
    solves produce bitwise-identical trajectories, and distinct shards never
    replay each other's noise.

    Dataset tables (``prob.data``) are BROADCAST, never sharded: every shard
    receives the full table set as replicated shard_map inputs (in_specs=P())
    and solves its trajectory chunk against the identical dataset, so
    sharded == local holds for data-driven problems too — and gradients
    w.r.t. table values flow through the shard_map (each shard contributes
    its trajectories' table cotangents; a mean-reducing loss psums them in
    its own backward pass).

    Gradients compose with sharding: pass ``sensitivity="adjoint"`` (plus
    ``adjoint_steps`` for adaptive stepping — see `solve_ensemble_local`) and
    `jax.grad` of a scalar loss over the sharded result differentiates
    through the shard_map — each shard runs its local checkpointed adjoint
    over its own trajectories (states need no collectives; zero-collective
    property preserved), and the transposes of the stats psums are the only
    cross-shard traffic in the backward pass.  Per-shard gradient
    contributions are assembled on the same trajectory sharding as (u0s, ps);
    a loss that mean-reduces over trajectories psums gradient accumulators
    exactly once, in ITS backward pass.
    """
    if mesh is None:
        return solve_ensemble_local(eprob, **kw)

    axes = _ensemble_axes(mesh, shard_axes)
    nshards = 1
    for a in axes:
        nshards *= mesh.shape[a]
    u0s, ps = eprob.materialize()
    N = u0s.shape[0]
    assert N % nshards == 0, (
        f"trajectories {N} must divide over {nshards} shards")
    n_local = N // nshards
    prob = eprob.prob
    spec = P(axes)
    base_offset = kw.pop("lane_offset", 0)

    # Dataset tables are BROADCAST, not sharded: every shard solves against
    # the identical dataset, so the leaves enter shard_map as explicit
    # replicated inputs (in_specs=P()) and the problem is rebuilt per shard.
    # Explicit — rather than closure-captured — so sharded == local holds by
    # construction AND `jax.grad` w.r.t. table values differentiates through
    # the shard_map (closure-captured tracers would be rejected).
    data = getattr(prob, "data", None)
    dleaves, dtreedef = data_flatten(data)

    def _shard_prob(dlv):
        if data is None:
            return prob
        return dataclasses.replace(
            prob, data=data_unflatten(dtreedef, dlv))

    if kw.get("ensemble") == "auto":
        # resolve BEFORE shard_map: timing cannot run under tracing, and all
        # shards must dispatch one program.  Tune once per host on a
        # local-shard-sized slice (each device solves n_local trajectories,
        # so that is the N whose crossover matters), broadcast host 0's
        # winner, and hand every shard the explicit choice.
        from .autotune import broadcast_decision, resolve_auto
        from .methods import get_method
        u0_loc, ps_loc = u0s[:n_local], ps[:n_local]
        sub = EnsembleProblem(prob, n_local, u0s=u0_loc, ps=ps_loc)
        tune_args = ("t0", "tf", "dt0", "saveat", "rtol", "atol", "adaptive",
                     "n_steps", "save_every", "max_iters", "event", "key",
                     "seed", "noise_table", "error_est", "w_reuse",
                     "linsolve", "sensitivity")
        tune_kw = {k: v for k, v in kw.items() if k in tune_args}
        dec = broadcast_decision(
            resolve_auto(sub, get_method(kw.get("alg", "tsit5")), **tune_kw))
        kw = dict(kw, ensemble=dec.strategy, backend=dec.backend)
        if kw.get("lane_tile") is None:
            kw["lane_tile"] = dec.lane_tile

    # step counters are per-trajectory vectors under the kernel strategy but
    # batch scalars under vmap/array — probe the local solve's result ranks
    # (trace only, no compile) so the out_specs match whatever dispatch
    # (explicit or auto-resolved above) actually returns
    shard_shapes = jax.eval_shape(
        lambda u, p, *dlv: solve_ensemble_local(
            EnsembleProblem(_shard_prob(dlv), n_local, u0s=u, ps=p),
            lane_offset=base_offset, **kw),
        jax.ShapeDtypeStruct((n_local,) + u0s.shape[1:], u0s.dtype),
        jax.ShapeDtypeStruct((n_local,) + ps.shape[1:], ps.dtype),
        *dleaves)
    per_traj_counts = shard_shapes.naccept.ndim > 0

    def local(u0c, pc, *dlv):
        # linear shard index in the same axis order the PartitionSpec uses,
        # -> this shard's first global trajectory index
        idx = jnp.asarray(0, jnp.uint32)
        for a in axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a).astype(jnp.uint32)
        sub = EnsembleProblem(_shard_prob(dlv), u0c.shape[0], u0s=u0c, ps=pc)
        res = solve_ensemble_local(sub, lane_offset=base_offset + idx * n_local,
                                   **kw)
        # per-shard scalars -> global via psum (lightweight stats only)
        nf, njac, nfact = res.nf, res.njac, res.nfact
        nacc, nrej = res.naccept, res.nreject
        for a in axes:
            nf = jax.lax.psum(nf, a)
            njac = jax.lax.psum(njac, a)
            nfact = jax.lax.psum(nfact, a)
            if not per_traj_counts:
                nacc = jax.lax.psum(nacc, a)
                nrej = jax.lax.psum(nrej, a)
        return res._replace(nf=nf, njac=njac, nfact=nfact,
                            naccept=nacc, nreject=nrej)

    count_spec = spec if per_traj_counts else P()
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(spec, spec) + (P(),) * len(dleaves),
                       out_specs=EnsembleResult(
                           ts=P(), us=spec, u_final=spec, t_final=spec,
                           naccept=count_spec, nreject=count_spec, nf=P(),
                           status=P(), njac=P(), nfact=P(),
                           steps_run=(None if shard_shapes.steps_run is None
                                      else spec)),
                       check_vma=False)
    if kw.get("sensitivity") is not None:
        # the bounded adjoint loop wraps segments in jax.checkpoint, which
        # lowers to closed_call — shard_map cannot evaluate that eagerly
        # ("Eager evaluation of closed_call inside a shard_map isn't yet
        # supported"), so stage the whole sharded solve through jit; under an
        # outer jit/grad this inlines and changes nothing
        fn = jax.jit(fn)
    return fn(u0s, ps, *dleaves)


def solve_ensemble_elastic(eprob: EnsembleProblem, alg="tsit5", *,
                           ckpt_dir: str, n_shards: int = 2,
                           resume: bool = False, chaos=None, **kw):
    """Fault-tolerant segmented ensemble solve — the elastic face of the
    front door.

    Wraps `repro.dist.elastic.ElasticSupervisor`: the run advances in
    bounded segments with periodic host-gathered carry snapshots through
    the atomic checkpoint layer, survives shard loss by re-sharding the
    unfinished tiles over the survivors (degradation ladder down to a
    single host, then a partial result with per-lane
    ``status == STATUS_SHARD_LOST``), and ``resume=True`` restores the
    newest snapshot — onto ANY `n_shards`, in the same process or a
    relaunched one.  A killed-and-resumed run is bitwise identical to an
    uninterrupted one (see the module docstring for the contract, and
    tests/test_elastic.py for the SIGKILL proof).

    Returns `repro.dist.elastic.ElasticResult` (host numpy per-lane finals
    + a fault-history report), not a device `EnsembleResult` — elasticity
    is a host-side supervision loop by construction.

    Keyword args beyond the supervisor's (tile_width, segment_steps,
    snapshot_every, max_failures, backoff_*, ...) mirror
    `solve_ensemble_local` (t0, tf, dt0, n_steps, adaptive, rtol, atol,
    event, seed, lane_offset, max_iters, ...).
    """
    from repro.dist.elastic import ElasticSupervisor
    sup = ElasticSupervisor(eprob, alg, ckpt_dir=ckpt_dir,
                            n_shards=n_shards, chaos=chaos, **kw)
    return sup.run(resume=resume)


def ensemble_moments(us: Array, mesh: Optional[Mesh] = None,
                     shard_axes: Optional[Sequence[str]] = None):
    """Mean/variance over the (possibly sharded) trajectory axis — the SDE
    Monte-Carlo reduction (§6.8). us: (N, ...) sharded on axis 0.

    Variance uses the centered two-pass form (psum the mean first, then psum
    the squared deviations): the textbook one-pass ``E[X²] − mean²`` loses
    ~2·log10(mean/std) digits to catastrophic cancellation — in f32 a GBM
    ensemble at drift 1.5 over a unit horizon (mean ≈ 4.5, std ≈ 0.05) has
    NO correct digits left and can even come back negative.  The clamp at 0
    guards the residual rounding of the centered sum."""
    if mesh is None:
        return jnp.mean(us, axis=0), jnp.maximum(jnp.var(us, axis=0), 0)

    axes = _ensemble_axes(mesh, shard_axes)
    spec = P(axes)

    def local(u):
        n_local = u.shape[0]
        s1 = jnp.sum(u, axis=0)
        n = jnp.asarray(n_local, u.dtype)
        for a in axes:
            s1 = jax.lax.psum(s1, a)
            n = jax.lax.psum(n, a)
        mean = s1 / n
        d = u - mean[None]
        s2c = jnp.sum(d * d, axis=0)
        for a in axes:
            s2c = jax.lax.psum(s2c, a)
        var = jnp.maximum(s2c / n, 0)
        return mean, var

    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec,),
                       out_specs=(P(), P()), check_vma=False)
    return fn(us)
