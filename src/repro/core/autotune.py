"""Autotuned dispatch: pick strategy/backend/lane_tile from measured time.

The paper's Fig. 4-6 crossovers (kernel overtakes array overtakes vmap as N
grows) move with method, state dim n, ensemble size N, dtype and device —
after PRs 1-5 the user had to hand-pick among 3 strategies x 2 backends x
`lane_tile` x `w_reuse` x `error_est`.  ``ensemble="auto"`` closes that gap:

  1. The solve's *configuration key* — ``(method, n, N-bucket, dtype,
     adaptive, events, w_reuse, error_est, device_kind)`` — is looked up in
     an in-memory + JSON profile cache (`default_cache_path`; see below).
  2. On a miss, a capability-pruned candidate set
     (`repro.core.methods.valid_dispatch`; vmap/array/kernel x xla/pallas x
     the `lane_tile` ladder from the §5.2 VMEM formula) is *timed on the
     real problem* at reduced N and a short horizon — median-of-k wall time
     with `block_until_ready` (`measure`, the same harness
     `benchmarks/common.py` re-exports, so tuner and paper figures share one
     methodology).
  3. The winner is persisted, so every later call — any process, including
     each host of a mesh-sharded `repro.core.api.solve_ensemble` —
     dispatches straight to it with one dict lookup of overhead.

Cache location: ``~/.cache/repro/autotune.json`` (respects
``XDG_CACHE_HOME``), overridable via ``REPRO_AUTOTUNE_CACHE`` or the
``cache_path=`` argument.  Entries are invalidated by construction when the
device changes (``device_kind`` is part of the key) and at lookup when the
recorded jax version differs.  ``REPRO_AUTOTUNE=0`` disables timing
entirely (CI / ``--dry`` runs): ``"auto"`` then falls back to the static
default (kernel/xla), as it also does under jit tracing, where wall time
cannot be measured — tune once eagerly and the cached winner is dispatched
even from inside jit, since the key is built from static shape/dtype data
only.
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile
import threading
import time
from typing import Any, Dict, Optional, Tuple

try:
    import fcntl
except ImportError:          # non-POSIX: single-process semantics only
    fcntl = None

import jax
import jax.numpy as jnp
import numpy as np

from .interp import data_signature, data_words
from .methods import MethodSpec, valid_dispatch
from .problem import EnsembleProblem

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
DISABLE_ENV = "REPRO_AUTOTUNE"
CACHE_VERSION = 1

# tuning cost knobs (env-overridable; see docs/architecture.md)
TUNE_MAX_N = int(os.environ.get("REPRO_AUTOTUNE_MAX_N", "4096"))
TUNE_REPEATS = int(os.environ.get("REPRO_AUTOTUNE_REPEATS", "3"))
TUNE_HORIZON_FRAC = float(os.environ.get("REPRO_AUTOTUNE_HORIZON", "0.25"))

DEFAULT_STRATEGY = ("kernel", "xla", None)   # the front door's static default


# ---------------------------------------------------------------------------
# timing harness — shared with benchmarks/common.py
# ---------------------------------------------------------------------------

def measure(fn, *args, repeats: int = 3, **kw) -> Dict[str, Any]:
    """Median-of-k wall timing with compile/warmup excluded.

    One untimed warmup call absorbs tracing + compilation; each timed repeat
    calls `jax.block_until_ready` on the result BEFORE the clock stops, so
    async dispatch cannot flatter the number.  Returns
    ``{"best", "median", "times"}`` in seconds — rank candidates by
    ``median`` (robust to scheduler noise), report ``best`` as the
    machine-capability figure.
    """
    jax.block_until_ready(fn(*args, **kw))
    times = []
    for _ in range(max(1, repeats)):
        tic = time.perf_counter()
        jax.block_until_ready(fn(*args, **kw))
        times.append(time.perf_counter() - tic)
    times.sort()
    return {"best": times[0], "median": times[len(times) // 2],
            "times": times}


# ---------------------------------------------------------------------------
# configuration key
# ---------------------------------------------------------------------------

def device_kind() -> str:
    d = jax.devices()[0]
    return f"{d.platform}:{getattr(d, 'device_kind', '?')}".replace(" ", "_")


def bucket_n(N: int) -> int:
    """Next power of two — nearby ensemble sizes share one cache entry."""
    b = 1
    while b < N:
        b *= 2
    return b


def resolved_flags(spec: MethodSpec, prob, *, adaptive, w_reuse, error_est,
                   event) -> Tuple[bool, bool, bool, str]:
    """Normalize the front door's None-means-family-default knobs to the
    concrete values dispatch will run with — the key must not split on
    spellings of the same configuration."""
    if spec.family == "rosenbrock":
        ad = True                      # the stiff engine is always adaptive
    elif adaptive is None:
        ad = spec.family == "erk" and spec.adaptive
    else:
        ad = bool(adaptive) and spec.adaptive
    wr = spec.w_reuse if w_reuse is None else bool(w_reuse)
    ee = "none"
    if spec.family == "sde" and ad:
        if error_est is not None:
            ee = str(error_est)
        else:
            diag = getattr(prob, "noise", None) == "diagonal"
            ee = ("embedded" if ("embedded" in spec.error_est and diag)
                  else "doubling")
    return ad, event is not None, wr, ee


def config_key(spec: MethodSpec, *, n: int, N: int, dtype, adaptive: bool,
               events: bool, w_reuse: bool, error_est: str,
               device: Optional[str] = None,
               sensitivity: Optional[str] = None,
               data_sig: str = "none", consts: bool = False) -> str:
    """Deterministic cache key — a readable ``k=v|...`` string (field order
    fixed), hashable across processes and debuggable in the JSON by eye.
    ``data_sig`` is the dataset-shape signature
    (`repro.core.interp.data_signature`): VMEM-resident tables shift the
    kernel crossovers (and the auto lane_tile), so a data-driven solve must
    not reuse the data-free profile of the same method.  ``consts`` marks
    a problem whose callbacks close over arrays: it has no Pallas
    candidate, so its profile is not that of the closure-free twin."""
    return "|".join((
        f"method={spec.name}",
        f"n={int(n)}",
        f"N={bucket_n(int(N))}",
        f"dtype={jnp.dtype(dtype).name}",
        f"adaptive={bool(adaptive)}",
        f"events={bool(events)}",
        f"w_reuse={bool(w_reuse)}",
        f"error_est={error_est}",
        f"sens={sensitivity or 'none'}",
        f"data={data_sig}",
        f"consts={bool(consts)}",
        f"device={device_kind() if device is None else device}"))


# ---------------------------------------------------------------------------
# profile cache (JSON file + in-memory layer)
# ---------------------------------------------------------------------------

_MEM: Dict[str, Dict[str, Any]] = {}   # cache-file path -> entries
_MEM_LOCK = threading.Lock()           # concurrent tuners (serve pool pumps)


def default_cache_path() -> str:
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    base = os.environ.get("XDG_CACHE_HOME", os.path.expanduser("~/.cache"))
    return os.path.join(base, "repro", "autotune.json")


def clear_memory_cache() -> None:
    """Drop the in-process cache layer (tests; the JSON file is untouched)."""
    with _MEM_LOCK:
        _MEM.clear()


def _read_file_entries(path: str) -> Dict[str, Any]:
    """Entries as currently on disk — never consults the in-memory layer."""
    entries: Dict[str, Any] = {}
    try:
        with open(path) as fh:
            data = json.load(fh)
        if isinstance(data, dict) and data.get("version") == CACHE_VERSION:
            entries = dict(data.get("entries", {}))
    except (OSError, ValueError):
        pass
    return entries


def _load_entries(path: str) -> Dict[str, Any]:
    with _MEM_LOCK:
        if path in _MEM:
            return _MEM[path]
    entries = _read_file_entries(path)
    with _MEM_LOCK:
        return _MEM.setdefault(path, entries)


def _save_entries(path: str, entries: Dict[str, Any]) -> None:
    """Persist `entries`, MERGING with concurrent writers.

    Two processes tuning different configs race on the JSON file: each did
    load -> add-own-key -> replace, and the last replace silently dropped the
    other's entry (a classic lost update).  The critical section below holds
    an `fcntl.flock` on a sidecar lock file while it re-reads the file,
    unions the disk entries under ours (our fresher timings win ties), and
    atomically replaces — so every writer's keys survive every interleaving.
    The merged view also refreshes the in-memory layer.
    """
    merged = dict(entries)
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        lock_fh = open(path + ".lock", "a+") if fcntl is not None else None
    except OSError:
        lock_fh = None
    try:
        if lock_fh is not None:
            try:
                fcntl.flock(lock_fh.fileno(), fcntl.LOCK_EX)
            except OSError:
                pass
        disk = _read_file_entries(path)
        merged = {**disk, **entries}
        payload = {"version": CACHE_VERSION, "entries": merged}
        try:
            fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                                       suffix=".tmp")
            with os.fdopen(fd, "w") as fh:
                json.dump(payload, fh, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except OSError:
            pass   # read-only FS etc: the in-memory layer still serves us
    finally:
        if lock_fh is not None:
            lock_fh.close()          # releases the flock
    with _MEM_LOCK:
        _MEM[path] = merged


# ---------------------------------------------------------------------------
# candidates
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Candidate:
    strategy: str
    backend: str
    lane_tile: Optional[int]

    @property
    def label(self) -> str:
        t = "" if self.lane_tile is None else f"/t{self.lane_tile}"
        return f"{self.strategy}/{self.backend}{t}"


@dataclasses.dataclass(frozen=True)
class Decision:
    """What ``ensemble="auto"`` resolved to, and why.

    source: "cache" (profile-cache hit), "tuned" (measured this call),
    "default" (timing unavailable/disabled — static kernel/xla fallback),
    or "only" (capability pruning left a single candidate: nothing to time).
    """
    strategy: str
    backend: str
    lane_tile: Optional[int]
    source: str
    key: str = ""
    timings: Tuple[Tuple[str, float], ...] = ()


def _family_work_words(spec: MethodSpec, prob, n: int, m: int,
                       w_reuse: bool) -> int:
    from repro.kernels.ensemble_kernel import (erk_work_words,
                                               rosenbrock_work_words,
                                               sde_work_words)
    if spec.family == "erk":
        return erk_work_words(n, m, spec.tableau.stages)
    if spec.family == "rosenbrock":
        return rosenbrock_work_words(n, m, stages=spec.rtableau.stages,
                                     w_reuse=w_reuse)
    return sde_work_words(n, m, prob.noise_dim())


def candidates(spec: MethodSpec, *, n: int, m: int, n_save: int, N: int,
               dtype, adaptive: bool, events: bool, w_reuse: bool,
               error_est: str, allow_pallas: bool = True, sensitivity=None,
               data: bool = False, data_words: int = 0):
    """Capability-pruned candidate list: every entry would be accepted by
    `solve_ensemble_local` (never time a combination that raises).
    ``array_eager`` is never a candidate — it exists to *reproduce* dispatch
    overhead, not to win.  ``sensitivity`` prunes combinations the AD rules
    reject (e.g. forward-mode on the Pallas backend).  ``data``/``data_words``
    describe the problem's dataset tables: the flag prunes methods that
    declare ``data_rhs=False``, and the word count is charged to the §5.2
    VMEM budget as a fixed (per-tile, not per-lane) footprint so the
    lane_tile ladder stays honest for data-driven kernels."""
    ee = error_est if error_est != "none" else None
    out = []

    def ok(strategy, backend):
        valid, _ = valid_dispatch(spec, strategy, backend, adaptive=adaptive,
                                  events=events, w_reuse=w_reuse,
                                  error_est=ee, sensitivity=sensitivity,
                                  data=data)
        return valid

    for strategy in ("vmap", "array"):
        if ok(strategy, "xla"):
            out.append(Candidate(strategy, "xla", None))
    if ok("kernel", "xla"):
        from repro.kernels.ensemble_kernel import lane_tile_ladder
        ladder = lane_tile_ladder(
            n, m, max(1, n_save), itemsize=jnp.dtype(dtype).itemsize,
            work_words=_family_work_words(spec, None, n, m, w_reuse)
            if spec.family != "sde" else None, N=N,
            fixed_words=data_words)
        for backend in ("xla", "pallas"):
            if backend == "pallas" and (not allow_pallas
                                        or not ok("kernel", "pallas")):
                continue
            for tile in ladder:
                out.append(Candidate("kernel", backend, int(tile)))
    return out


# ---------------------------------------------------------------------------
# resolve
# ---------------------------------------------------------------------------

def _disabled() -> bool:
    return os.environ.get(DISABLE_ENV, "1").lower() in ("0", "off", "false",
                                                        "disabled")


def _is_traced(*vals) -> bool:
    return any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves(vals))


def _captures_constants(prob, event, u0s, ps) -> bool:
    """True when a callback the Pallas kernel would trace closes over an
    array (a jaxpr constant) — e.g. a `bind_problem_data` closure or an RHS
    that captures a coefficient array.  `pallas_call` refuses captured
    constants (arrays enter a kernel only as explicit inputs, which is what
    ``prob.data`` is for), so such a problem has no Pallas candidate.
    Traced on abstract values, so it also answers under jit."""
    u = jax.ShapeDtypeStruct(u0s.shape[1:], u0s.dtype)
    p = jax.ShapeDtypeStruct(ps.shape[1:], ps.dtype)
    t = jax.ShapeDtypeStruct((), u0s.dtype)
    data = getattr(prob, "data", None)
    args = (u, p, t) if data is None else (u, p, t, jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
        data))
    fns = [(getattr(prob, name, None), args) for name in ("f", "g", "jac")]
    if event is not None:
        fns += [(event.condition, (u, p, t)), (event.affect, (u, p, t))]
    return any(jax.make_jaxpr(fn)(*a).consts for fn, a in fns
               if fn is not None)


def _tuning_slice(u0s, ps, N: int):
    """Evenly-strided subsample of the real ensemble (parameter sweeps are
    usually ordered; a head slice would tune on an unrepresentative corner)."""
    full = u0s.shape[0]
    if N >= full:
        return u0s, ps
    idx = np.linspace(0, full - 1, N).round().astype(int)
    return u0s[idx], ps[idx]


def resolve_auto(eprob: EnsembleProblem, spec: MethodSpec, *, t0=None,
                 tf=None, dt0=1e-2, saveat=None, rtol=1e-6, atol=1e-6,
                 adaptive=None, n_steps=None, save_every=1, max_iters=100_000,
                 event=None, key=None, seed=None, noise_table=None,
                 error_est=None, w_reuse=None, linsolve="jnp",
                 sensitivity=None, cache_path: Optional[str] = None,
                 repeats: Optional[int] = None) -> Decision:
    """Resolve ``ensemble="auto"`` to a concrete (strategy, backend,
    lane_tile) `Decision` — cache hit, fresh micro-benchmark, or static
    fallback.  Accepts the front door's kwargs verbatim; see the module
    docstring for the mechanism and `solve_ensemble_local` for wiring."""
    prob = eprob.prob
    u0s, ps = eprob.materialize()
    t0 = prob.tspan[0] if t0 is None else t0
    tf = prob.tspan[1] if tf is None else tf
    N, n = u0s.shape
    m = ps.shape[1]
    ad, ev, wr, ee = resolved_flags(spec, prob, adaptive=adaptive,
                                    w_reuse=w_reuse, error_est=error_est,
                                    event=event)
    pdata = getattr(prob, "data", None)
    consts = _captures_constants(prob, event, u0s, ps)
    ckey = config_key(spec, n=n, N=N, dtype=u0s.dtype, adaptive=ad,
                      events=ev, w_reuse=wr, error_est=ee,
                      sensitivity=sensitivity,
                      data_sig=data_signature(pdata), consts=consts)
    path = cache_path or default_cache_path()

    # 1. cache (works under jit too: the key is static shape/dtype data).
    # A cached winner may predate an AD request — re-check it against the
    # sensitivity rules and fall through to a constrained re-tune if the
    # cached combination would be rejected by the front door.
    entries = _load_entries(path)
    hit = entries.get(ckey)
    if hit is not None and hit.get("jax") == jax.__version__:
        sens_ok, _ = valid_dispatch(spec, hit["strategy"], hit["backend"],
                                    adaptive=ad, events=ev, w_reuse=wr,
                                    error_est=ee if ee != "none" else None,
                                    sensitivity=sensitivity)
        if sens_ok:
            return Decision(hit["strategy"], hit["backend"], hit["lane_tile"],
                            source="cache", key=ckey)

    # 2. timing unavailable -> static default
    if (_disabled() or dt0 is None
            or _is_traced(u0s, ps, t0, tf, dt0, saveat, seed, key, pdata)):
        return Decision(*DEFAULT_STRATEGY, source="default", key=ckey)

    # 3. candidate set (capability-pruned)
    S_real = (int(np.asarray(saveat).shape[0]) if saveat is not None
              else max(1, (n_steps or 1) // max(1, save_every)))
    try:
        concrete_seed = 0 if seed is None and key is None else int(
            jnp.asarray(key)[-1] if seed is None else seed)
        allow_pallas = True
    except (TypeError, jax.errors.TracerIntegerConversionError,
            jax.errors.ConcretizationTypeError):
        concrete_seed, allow_pallas = 0, spec.family != "sde"
    allow_pallas = allow_pallas and not consts
    cands = candidates(spec, n=n, m=m, n_save=S_real, N=min(N, TUNE_MAX_N),
                       dtype=u0s.dtype, adaptive=ad, events=ev, w_reuse=wr,
                       error_est=ee, allow_pallas=allow_pallas,
                       sensitivity=sensitivity, data=pdata is not None,
                       data_words=data_words(pdata))
    if not cands:
        return Decision(*DEFAULT_STRATEGY, source="default", key=ckey)
    if len(cands) == 1:
        c = cands[0]
        return Decision(c.strategy, c.backend, c.lane_tile, source="only",
                        key=ckey)

    # 4. reduced problem: real RHS/params, subsampled N, short horizon
    N_t = min(N, TUNE_MAX_N)
    u0s_t, ps_t = _tuning_slice(u0s, ps, N_t)
    sub = EnsembleProblem(prob, N_t, u0s=u0s_t, ps=ps_t)
    span = float(tf) - float(t0)
    fixed_dt = ((spec.family == "sde" and not ad)
                or (spec.family == "erk" and not ad))
    tune_kw = dict(t0=t0, rtol=rtol, atol=atol, adaptive=adaptive,
                   max_iters=min(max_iters, 20_000), event=event,
                   seed=concrete_seed, error_est=error_est, w_reuse=w_reuse,
                   linsolve=linsolve)
    if fixed_dt:
        ns_full = n_steps if n_steps is not None else max(
            1, int(round(span / float(dt0))))
        ns = max(1, int(round(ns_full * TUNE_HORIZON_FRAC)))
        tune_kw.update(dt0=dt0, n_steps=ns, save_every=ns, saveat=None,
                       tf=float(t0) + ns * float(dt0))
    else:
        tf_t = float(t0) + max(span * TUNE_HORIZON_FRAC,
                               min(span, 16.0 * float(dt0)))
        tune_kw.update(dt0=dt0, saveat=None, tf=tf_t, n_steps=None)

    # 5. time everything; median-of-k, block_until_ready inside the clock
    from .ensemble import solve_ensemble_local
    k = TUNE_REPEATS if repeats is None else repeats
    timings = []
    for c in cands:
        def run(u0s_, ps_, _c=c):
            ep = EnsembleProblem(prob, u0s_.shape[0], u0s=u0s_, ps=ps_)
            return solve_ensemble_local(ep, alg=spec, ensemble=_c.strategy,
                                        backend=_c.backend,
                                        lane_tile=_c.lane_tile,
                                        **tune_kw).u_final
        # a candidate that raises is a bug (on a chip: a kernel the compiler
        # refused), never a candidate that merely lost — let it surface
        stat = measure(jax.jit(run), u0s_t, ps_t, repeats=k)
        timings.append((c, stat["median"]))
    winner, _ = min(timings, key=lambda ct: ct[1])

    # 6. persist
    entry = {"strategy": winner.strategy, "backend": winner.backend,
             "lane_tile": winner.lane_tile, "jax": jax.__version__,
             "tuned_at_N": int(N_t),
             "timings": {c.label: t for c, t in timings}}
    entries = dict(_load_entries(path))
    entries[ckey] = entry
    _save_entries(path, entries)
    return Decision(winner.strategy, winner.backend, winner.lane_tile,
                    source="tuned", key=ckey,
                    timings=tuple((c.label, t) for c, t in timings))


def broadcast_decision(dec: Decision) -> Decision:
    """Multi-host agreement: host 0's decision wins everywhere.  A sharded
    solve must dispatch identically on every host (shard_map traces one
    program); timing jitter could otherwise split the fleet.  Single-process
    runs return the decision unchanged."""
    if jax.process_count() == 1:
        return dec
    # no fallback to the local decision: hosts that disagree would trace
    # different programs, so a failed broadcast must fail the solve
    from jax.experimental import multihost_utils
    from .methods import BACKENDS, STRATEGIES
    payload = jnp.asarray([STRATEGIES.index(dec.strategy),
                           BACKENDS.index(dec.backend),
                           -1 if dec.lane_tile is None
                           else int(dec.lane_tile)], jnp.int32)
    got = np.asarray(multihost_utils.broadcast_one_to_all(payload))
    return Decision(STRATEGIES[int(got[0])], BACKENDS[int(got[1])],
                    None if int(got[2]) < 0 else int(got[2]),
                    source=dec.source, key=dec.key)
