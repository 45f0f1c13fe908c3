"""Autotuned dispatch (`ensemble="auto"`, repro.core.autotune): key schema,
profile-cache round-trips, capability pruning, bitwise parity with explicit
dispatch, the graceful static fallback when timing is unavailable, and no
fallback at all when a candidate fails to run.

The CI bench-smoke job runs exactly this module as its autotune leg: every
test tunes into a pytest tmpdir cache (never ~/.cache), and the round-trip
test asserts the second resolve is a PURE cache hit — zero timing calls.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.de_problems import lorenz_ensemble
from repro.core import EnsembleProblem, get_method, solve_ensemble_local
from repro.core import autotune as at
from repro.core.api import solve_ensemble
from repro.core.methods import valid_dispatch

SOLVE_KW = dict(t0=0.0, tf=0.5, dt0=1e-2, adaptive=True, rtol=1e-5,
                atol=1e-5)


@pytest.fixture
def cache(tmp_path):
    at.clear_memory_cache()
    yield str(tmp_path / "autotune.json")
    at.clear_memory_cache()


@pytest.fixture
def counted_measure(monkeypatch):
    calls = {"n": 0}
    real = at.measure

    def counting(fn, *a, **k):
        calls["n"] += 1
        return real(fn, *a, **k)

    monkeypatch.setattr(at, "measure", counting)
    return calls


# ---------------------------------------------------------------------------
# key schema
# ---------------------------------------------------------------------------

def test_config_key_deterministic_and_bucketed():
    spec = get_method("tsit5")
    kw = dict(n=3, dtype=jnp.float32, adaptive=True, events=False,
              w_reuse=False, error_est="none", device="cpu:x")
    k1 = at.config_key(spec, N=1000, **kw)
    assert k1 == at.config_key(spec, N=1000, **kw)   # deterministic
    assert k1 == at.config_key(spec, N=600, **kw)    # same power-of-2 bucket
    assert k1 != at.config_key(spec, N=5000, **kw)   # different bucket
    kw64 = dict(kw, dtype=jnp.float64)
    assert k1 != at.config_key(spec, N=1000, **kw64)  # dtype splits the key
    assert "method=tsit5" in k1 and "device=cpu:x" in k1


def test_resolved_flags_normalize_family_defaults():
    erk, rb, sde = (get_method(a) for a in ("tsit5", "rodas4", "em"))
    prob = lorenz_ensemble(4).prob
    # erk: None means adaptive; rk4 (no pair) cannot be adaptive
    assert at.resolved_flags(erk, prob, adaptive=None, w_reuse=None,
                             error_est=None, event=None)[0] is True
    rk4 = get_method("rk4")
    assert at.resolved_flags(rk4, prob, adaptive=None, w_reuse=None,
                             error_est=None, event=None)[0] is False
    # rosenbrock: always adaptive; sde: fixed-dt by default
    assert at.resolved_flags(rb, prob, adaptive=None, w_reuse=None,
                             error_est=None, event=None)[0] is True
    assert at.resolved_flags(sde, prob, adaptive=None, w_reuse=None,
                             error_est=None, event=None)[0] is False


# ---------------------------------------------------------------------------
# cache round-trip
# ---------------------------------------------------------------------------

def test_tune_then_pure_cache_hits(cache, counted_measure):
    ep = lorenz_ensemble(32)
    spec = get_method("tsit5")
    dec = at.resolve_auto(ep, spec, cache_path=cache, **SOLVE_KW)
    assert dec.source == "tuned"
    assert counted_measure["n"] > 1          # several candidates were timed
    n_timed = counted_measure["n"]

    # in-memory hit: no re-timing
    dec2 = at.resolve_auto(ep, spec, cache_path=cache, **SOLVE_KW)
    assert dec2.source == "cache"
    assert counted_measure["n"] == n_timed

    # cold-process reload from the JSON file: still no re-timing
    at.clear_memory_cache()
    dec3 = at.resolve_auto(ep, spec, cache_path=cache, **SOLVE_KW)
    assert dec3.source == "cache"
    assert counted_measure["n"] == n_timed
    assert (dec3.strategy, dec3.backend, dec3.lane_tile) == (
        dec.strategy, dec.backend, dec.lane_tile)

    with open(cache) as fh:
        data = json.load(fh)
    assert data["version"] == at.CACHE_VERSION
    entry = data["entries"][dec.key]
    assert entry["jax"] == jax.__version__
    assert entry["timings"]                  # medians persisted per candidate


def test_stale_jax_version_invalidates(cache, monkeypatch):
    ep = lorenz_ensemble(32)
    spec = get_method("tsit5")
    dec = at.resolve_auto(ep, spec, cache_path=cache, **SOLVE_KW)
    with open(cache) as fh:
        data = json.load(fh)
    data["entries"][dec.key]["jax"] = "0.0.stale"
    with open(cache, "w") as fh:
        json.dump(data, fh)
    at.clear_memory_cache()
    monkeypatch.setenv(at.DISABLE_ENV, "0")   # timing off: a stale entry must
    dec2 = at.resolve_auto(ep, spec, cache_path=cache, **SOLVE_KW)
    assert dec2.source == "default"           # NOT be served as a cache hit


# ---------------------------------------------------------------------------
# auto == explicit dispatch, bitwise
# ---------------------------------------------------------------------------

def test_auto_bitwise_equals_explicit_winner(cache, monkeypatch):
    monkeypatch.setenv(at.CACHE_ENV, cache)
    ep = lorenz_ensemble(48)
    saveat = jnp.asarray([0.25, 0.5])
    kw = dict(t0=0.0, tf=0.5, dt0=1e-2, saveat=saveat, rtol=1e-5, atol=1e-5)
    r_auto = solve_ensemble_local(ep, alg="tsit5", ensemble="auto", **kw)
    dec = at.resolve_auto(ep, get_method("tsit5"), cache_path=cache,
                          **dict(kw, saveat=saveat))
    assert dec.source == "cache"              # the solve above tuned it
    r_exp = solve_ensemble_local(ep, alg="tsit5", ensemble=dec.strategy,
                                 backend=dec.backend,
                                 lane_tile=dec.lane_tile, **kw)
    assert np.array_equal(np.asarray(r_auto.us), np.asarray(r_exp.us))
    assert np.array_equal(np.asarray(r_auto.u_final),
                          np.asarray(r_exp.u_final))
    assert np.array_equal(np.asarray(r_auto.t_final),
                          np.asarray(r_exp.t_final))


def test_warm_cache_auto_dispatches_inside_jit(cache, monkeypatch,
                                               counted_measure):
    monkeypatch.setenv(at.CACHE_ENV, cache)
    ep = lorenz_ensemble(32)
    prob = ep.prob
    u0s, ps = ep.materialize()
    kw = dict(t0=0.0, tf=0.5, dt0=1e-2, rtol=1e-5, atol=1e-5)
    # tune once, eagerly
    solve_ensemble_local(ep, alg="tsit5", ensemble="auto", **kw)
    n_timed = counted_measure["n"]
    assert n_timed > 0

    def run(u0s_, ps_):
        sub = EnsembleProblem(prob, u0s_.shape[0], u0s=u0s_, ps=ps_)
        return solve_ensemble_local(sub, alg="tsit5", ensemble="auto",
                                    **kw).u_final

    out = jax.jit(run)(u0s, ps)               # key is static: cache hit works
    assert counted_measure["n"] == n_timed    # ... with zero timing under jit
    dec = at.resolve_auto(ep, get_method("tsit5"), cache_path=cache, **kw)
    ref = solve_ensemble_local(ep, alg="tsit5", ensemble=dec.strategy,
                               backend=dec.backend, lane_tile=dec.lane_tile,
                               **kw).u_final
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_mesh_solve_ensemble_accepts_auto(cache, monkeypatch):
    monkeypatch.setenv(at.CACHE_ENV, cache)
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    ep = lorenz_ensemble(32)
    kw = dict(t0=0.0, tf=0.5, dt0=1e-2, rtol=1e-5, atol=1e-5)
    r = solve_ensemble(ep, mesh=mesh, ensemble="auto", **kw)
    dec = at.resolve_auto(ep, get_method("tsit5"), cache_path=cache, **kw)
    assert dec.source == "cache"              # tuned once, before shard_map
    ref = solve_ensemble_local(ep, ensemble=dec.strategy,
                               backend=dec.backend,
                               lane_tile=dec.lane_tile, **kw)
    np.testing.assert_allclose(np.asarray(r.u_final),
                               np.asarray(ref.u_final), rtol=1e-12)


# ---------------------------------------------------------------------------
# capability pruning
# ---------------------------------------------------------------------------

def test_candidates_are_all_dispatchable():
    cases = [
        (get_method("tsit5"), dict(adaptive=True, events=False,
                                   w_reuse=False, error_est="none")),
        (get_method("rodas4"), dict(adaptive=True, events=False,
                                    w_reuse=True, error_est="none")),
        (get_method("em"), dict(adaptive=False, events=False,
                                w_reuse=False, error_est="none")),
        (get_method("em"), dict(adaptive=True, events=True,
                                w_reuse=False, error_est="embedded")),
    ]
    for spec, flags in cases:
        cands = at.candidates(spec, n=3, m=3, n_save=4, N=64,
                              dtype=jnp.float32, **flags)
        assert cands, f"no candidates for {spec.name} {flags}"
        for c in cands:
            assert c.strategy != "array_eager"   # never a tuning candidate
            ok, why = valid_dispatch(
                spec, c.strategy, c.backend, adaptive=flags["adaptive"],
                events=flags["events"], w_reuse=flags["w_reuse"],
                error_est=None if flags["error_est"] == "none"
                else flags["error_est"])
            assert ok, f"{spec.name}: {c.label} invalid: {why}"
            if c.backend == "pallas":
                assert c.strategy == "kernel"


def test_pruning_rejects_impossible_combos():
    # non-rosenbrock w_reuse: nothing to tune
    assert at.candidates(get_method("tsit5"), n=3, m=3, n_save=1, N=64,
                         dtype=jnp.float32, adaptive=True, events=False,
                         w_reuse=True, error_est="none") == []
    # estimator the method does not ship
    assert at.candidates(get_method("heun_strat"), n=2, m=2, n_save=1, N=64,
                         dtype=jnp.float32, adaptive=True, events=False,
                         w_reuse=False, error_est="embedded") == []
    ok, _ = valid_dispatch(get_method("tsit5"), "array", "pallas")
    assert not ok                              # pallas is kernel-only
    ok, _ = valid_dispatch(get_method("rodas4"), "array_eager")
    assert not ok                              # array_eager is erk-only


def test_lane_tile_ladder_brackets_formula():
    from repro.kernels.ensemble_kernel import (LANE_WIDTH, auto_lane_tile,
                                               lane_tile_ladder)
    ladder = lane_tile_ladder(3, 3, 8)
    auto = auto_lane_tile(3, 3, 8)
    assert auto in ladder and LANE_WIDTH in ladder
    assert list(ladder) == sorted(set(ladder))   # deduped, ascending
    # clamped to the padded ensemble width: a small N collapses the ladder
    assert lane_tile_ladder(3, 3, 8, N=64) == (64,)


# ---------------------------------------------------------------------------
# graceful fallback
# ---------------------------------------------------------------------------

def test_disabled_env_falls_back_to_static_default(cache, monkeypatch,
                                                   counted_measure):
    monkeypatch.setenv(at.DISABLE_ENV, "0")
    ep = lorenz_ensemble(32)
    dec = at.resolve_auto(ep, get_method("tsit5"), cache_path=cache,
                          **SOLVE_KW)
    assert (dec.strategy, dec.backend, dec.lane_tile) == at.DEFAULT_STRATEGY
    assert dec.source == "default"
    assert counted_measure["n"] == 0           # nothing was timed
    # the front door still works end to end with timing disabled
    r = solve_ensemble_local(ep, alg="tsit5", ensemble="auto", **SOLVE_KW)
    assert int(r.status) == 0


def test_failing_candidate_raises_instead_of_vanishing(cache, monkeypatch):
    """A candidate that raises (on a chip: a kernel the compiler refused)
    fails the resolve; it must not drop out of the ranking in silence."""
    real = at.measure
    calls = {"n": 0}

    def broken_second(fn, *a, **k):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("kernel refused")
        return real(fn, *a, **k)

    monkeypatch.setattr(at, "measure", broken_second)
    with pytest.raises(RuntimeError, match="kernel refused"):
        at.resolve_auto(lorenz_ensemble(32), get_method("tsit5"),
                        cache_path=cache, **SOLVE_KW)
    assert not os.path.exists(cache)           # nothing half-tuned persisted


def test_captured_array_prunes_pallas_candidates(cache, monkeypatch):
    """An RHS that closes over an array hands pallas_call a captured
    constant, which it refuses: such a problem tunes over the XLA
    candidates only (pruned, not raised), and ``"auto"`` still solves it."""
    import dataclasses
    ep = lorenz_ensemble(32)
    u0s, ps = ep.materialize()
    scale = jnp.ones((3,), u0s.dtype)

    def f_captured(u, p, t):
        return ep.prob.f(u, p, t) * scale.reshape((3,) + (1,) * (u.ndim - 1))

    cap = EnsembleProblem(dataclasses.replace(ep.prob, f=f_captured), 32,
                          u0s=u0s, ps=ps)
    spec = get_method("tsit5")
    monkeypatch.setenv(at.CACHE_ENV, cache)
    plain = at.resolve_auto(ep, spec, **SOLVE_KW)
    dec = at.resolve_auto(cap, spec, **SOLVE_KW)
    # a separate profile: the closure-free twin's Pallas winner is never
    # handed to the problem the kernel refuses
    assert "consts=False" in plain.key and "consts=True" in dec.key
    assert plain.source == dec.source == "tuned"
    assert any("pallas" in label for label, _ in plain.timings)
    assert dec.timings and not any("pallas" in label
                                   for label, _ in dec.timings)
    # the front door dispatches the cached (XLA) winner and solves
    r = solve_ensemble_local(cap, alg="tsit5", ensemble="auto", **SOLVE_KW)
    assert int(r.status) == 0


def test_cold_cache_under_jit_falls_back(cache, monkeypatch,
                                         counted_measure):
    monkeypatch.setenv(at.CACHE_ENV, cache)
    ep = lorenz_ensemble(32)
    prob = ep.prob
    u0s, ps = ep.materialize()

    def run(u0s_, ps_):
        sub = EnsembleProblem(prob, u0s_.shape[0], u0s=u0s_, ps=ps_)
        return solve_ensemble_local(sub, alg="tsit5", ensemble="auto",
                                    t0=0.0, tf=0.5, dt0=1e-2).u_final

    out = jax.jit(run)(u0s, ps)                # cold cache + tracers: no
    assert counted_measure["n"] == 0           # timing, static default
    ref = solve_ensemble_local(ep, alg="tsit5", ensemble=at.DEFAULT_STRATEGY[0],
                               backend=at.DEFAULT_STRATEGY[1],
                               t0=0.0, tf=0.5, dt0=1e-2).u_final
    assert np.array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# concurrent writers (the serve/mesh multi-process tuning scenario)
# ---------------------------------------------------------------------------

_WRITER_SCRIPT = r"""
import os, sys, time
from repro.core import autotune as at

path, key, order = sys.argv[1], sys.argv[2], sys.argv[3]
sdir = os.path.dirname(path)

def wait_for(*names, timeout=60.0):
    t0 = time.monotonic()
    while not all(os.path.exists(os.path.join(sdir, n)) for n in names):
        if time.monotonic() - t0 > timeout:
            sys.exit(3)
        time.sleep(0.01)

# classic lost-update shape: BOTH processes read the (empty) file, then each
# adds its own key and replaces.  The barrier files make the interleaving
# deterministic: loads strictly before either save, saves strictly ordered.
entries = dict(at._load_entries(path))
entries[key] = {"strategy": "kernel", "backend": "xla", "lane_tile": None,
                "jax": "test", "tuned_at_N": 1, "timings": {}}
open(os.path.join(sdir, "ready_" + key), "w").close()
wait_for("ready_cfgA", "ready_cfgB")
if order == "second":
    wait_for("saved_first")
at._save_entries(path, entries)
if order == "first":
    open(os.path.join(sdir, "saved_first"), "w").close()
"""


def test_concurrent_writers_merge_not_last_wins(tmp_path):
    """Two processes tune different configs; the later writer must MERGE,
    not clobber — both entries survive in the JSON."""
    import subprocess
    import sys

    path = str(tmp_path / "autotune.json")
    src = os.path.join(os.path.dirname(at.__file__), "..", "..")
    env = {**os.environ,
           "PYTHONPATH": os.path.abspath(src)
           + os.pathsep + os.environ.get("PYTHONPATH", "")}
    procs = [subprocess.Popen(
        [sys.executable, "-c", _WRITER_SCRIPT, path, key, order], env=env)
        for key, order in (("cfgA", "first"), ("cfgB", "second"))]
    for p in procs:
        assert p.wait(timeout=300) == 0
    with open(path) as fh:
        data = json.load(fh)
    assert set(data["entries"]) == {"cfgA", "cfgB"}, (
        "last writer dropped the concurrent entry")
    # a fresh in-process load (cold memory layer) sees the union too
    at.clear_memory_cache()
    assert set(at._load_entries(path)) == {"cfgA", "cfgB"}


# ---------------------------------------------------------------------------
# persistent compile cache placement (chip entry points)
# ---------------------------------------------------------------------------

_CACHE_SCRIPT = r"""
import os, sys
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
from repro.launch.compile_cache import use_compile_cache
path = use_compile_cache(root=sys.argv[1])
jax.jit(lambda x: x * 2 + 1)(jnp.arange(4.0)).block_until_ready()
print(path)
print(os.environ["REPRO_AUTOTUNE_CACHE"])
"""


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_lands_in_env_dir_or_checkout(tmp_path, from_env):
    """`use_compile_cache` keeps the cache where JAX_COMPILATION_CACHE_DIR
    says, else in `.jax_cache/` under the checkout root; the autotune
    profile cache follows it.  Run in a child (CPU only) because the cache
    directory is process-global JAX state."""
    import subprocess
    import sys

    root = tmp_path / "checkout"
    env_dir = tmp_path / "env_cache"
    src = os.path.join(os.path.dirname(at.__file__), "..", "..")
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", at.CACHE_ENV)}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.abspath(src))
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT, str(root)],
                         env=env, capture_output=True, text=True,
                         timeout=300, check=True).stdout.split()
    want = env_dir if from_env else root / ".jax_cache"
    assert out == [str(want), str(want / "autotune.json")]
    assert any(want.iterdir())                 # compiled entries written
    if from_env:
        assert not (root / ".jax_cache").exists()
