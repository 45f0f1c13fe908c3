"""`EnsembleResult.steps_run`: the loop iterations each trajectory's lane
tile ran, the seventh row of the kernel's stats block.  Per lane it is the
maximum of `naccept + nreject` over the lane's tile (every lane runs until
the tile's slowest is done), or the static step count on fixed-step paths;
the Pallas kernel and its XLA twin report the same counts."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.de_problems import (gbm_problem, lorenz_problem,
                                       vdp_ensemble)
from repro.core import EnsembleProblem, get_tableau
from repro.core.ensemble import solve_ensemble_local


def _random_rho_lorenz(N, seed=0, dtype=jnp.float64):
    rho = np.random.default_rng(seed).uniform(0.0, 21.0, N)
    ps = jnp.stack([jnp.full((N,), 10.0, dtype), jnp.asarray(rho, dtype),
                    jnp.full((N,), 8.0 / 3.0, dtype)], axis=1)
    return EnsembleProblem(lorenz_problem(dtype), N, ps=ps)


def _tile_max(values, tile):
    """Each lane's tile maximum, tiles of `tile` lanes from lane 0."""
    v = np.asarray(values)
    out = np.empty_like(v)
    for lo in range(0, v.size, tile):
        out[lo:lo + tile] = v[lo:lo + tile].max()
    return out


def _attempts(r):
    return np.asarray(r.naccept) + np.asarray(r.nreject)


def _assert_tile_max(r, tile):
    run = np.asarray(r.steps_run)
    assert run.dtype == np.int32 and run.shape == np.asarray(r.naccept).shape
    assert np.all(run >= _attempts(r))
    np.testing.assert_array_equal(run, _tile_max(_attempts(r), tile))


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_tsit5_adaptive_steps_run_is_tile_max(backend):
    # 40 lanes in tiles of 16: the last tile is ragged (edge-padded lanes
    # repeat lane 39, so they never raise its maximum)
    ep = _random_rho_lorenz(40)
    r = solve_ensemble_local(ep, alg="tsit5", ensemble="kernel",
                             backend=backend, lane_tile=16, t0=0.0, tf=1.0,
                             dt0=1e-3, rtol=1e-5, atol=1e-5)
    _assert_tile_max(r, 16)
    # random rho spreads the attempts, so lockstep lanes idle
    assert _attempts(r).sum() < np.asarray(r.steps_run).sum()


def test_pallas_and_xla_steps_run_agree():
    ep = _random_rho_lorenz(24, seed=3)
    kw = dict(alg="tsit5", ensemble="kernel", lane_tile=8, t0=0.0, tf=1.0,
              dt0=1e-3, rtol=1e-6, atol=1e-6,
              saveat=jnp.linspace(0.25, 1.0, 4))
    rp = solve_ensemble_local(ep, backend="pallas", **kw)
    rx = solve_ensemble_local(ep, backend="xla", **kw)
    np.testing.assert_array_equal(np.asarray(rp.naccept),
                                  np.asarray(rx.naccept))
    np.testing.assert_array_equal(np.asarray(rp.steps_run),
                                  np.asarray(rx.steps_run))


def test_fixed_step_erk_runs_n_steps():
    ep = _random_rho_lorenz(20)
    r = solve_ensemble_local(ep, alg="tsit5", ensemble="kernel",
                             backend="pallas", lane_tile=8, t0=0.0, tf=1.0,
                             dt0=1e-2, adaptive=False, n_steps=100,
                             save_every=25)
    np.testing.assert_array_equal(np.asarray(r.steps_run), 100)
    np.testing.assert_array_equal(np.asarray(r.naccept), 100)


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_em_runs_n_steps(backend):
    ep = EnsembleProblem(gbm_problem(r=1.5, v=0.2, dtype=jnp.float64), 12)
    r = solve_ensemble_local(ep, alg="em", ensemble="kernel", backend=backend,
                             lane_tile=4, t0=0.0, tf=1.0, dt0=0.025,
                             n_steps=40, save_every=40, seed=7)
    np.testing.assert_array_equal(np.asarray(r.steps_run), 40)
    assert np.asarray(r.steps_run).shape == (12,)


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_rosenbrock_steps_run_is_tile_max(backend):
    ep = vdp_ensemble(12, mu_range=(1.0, 40.0))
    r = solve_ensemble_local(ep, alg="rosenbrock23", ensemble="kernel",
                             backend=backend, lane_tile=4, dt0=1e-3,
                             rtol=1e-5, atol=1e-5)
    assert int(r.status) == 0
    _assert_tile_max(r, 4)
    assert len(set(_attempts(r).tolist())) > 1


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_adaptive_sde_steps_run_is_tile_max(backend):
    # whole tiles: a ragged tile's padded lanes draw noise of their own
    # global lane indices, so their attempts (not reported) can set the
    # tile's count
    ep = EnsembleProblem(gbm_problem(r=1.5, v=0.3, dtype=jnp.float64), 12)
    r = solve_ensemble_local(ep, alg="em", ensemble="kernel", backend=backend,
                             lane_tile=4, t0=0.0, tf=1.0, dt0=0.05,
                             adaptive=True, rtol=1e-3, atol=1e-5, seed=11)
    assert int(r.status) == 0
    _assert_tile_max(r, 4)
    assert len(set(_attempts(r).tolist())) > 1


def test_staged_launch_sums_its_segments():
    """Fixed dt 2^-6 over [0, 1] in four segments of 16 steps each: the
    staged steps_run is their sum, as the single launch counts it."""
    from repro.kernels.tsit5.ops import solve_ensemble_pallas

    ep = _random_rho_lorenz(8, dtype=jnp.float32)
    u0s, ps = ep.materialize()
    kw = dict(t0=0.0, tf=1.0, dt0=float(2.0 ** -6),
              saveat=jnp.asarray([0.25, 0.5, 0.75, 1.0], jnp.float32),
              rtol=1e-5, atol=1e-5, adaptive=False, lane_tile=8)
    tab = get_tableau("tsit5")
    one = solve_ensemble_pallas(ep.prob, u0s, ps, tab, save_chunks=1, **kw)
    four = solve_ensemble_pallas(ep.prob, u0s, ps, tab, save_chunks=4, **kw)
    np.testing.assert_array_equal(np.asarray(one.steps_run), 64)
    np.testing.assert_array_equal(np.asarray(four.steps_run), 64)
    # adaptive segments: each counts its own tile maximum, so the sum is at
    # least every lane's attempts over the whole run
    kw.update(adaptive=True, dt0=1e-3)
    three = solve_ensemble_pallas(ep.prob, u0s, ps, tab, save_chunks=3,
                                  **dict(kw, saveat=jnp.linspace(
                                      0.1, 1.0, 10, dtype=jnp.float32)))
    run = np.asarray(three.steps_run)
    assert np.all(run >= _attempts(three)) and len(set(run.tolist())) == 1


@pytest.mark.parametrize("ensemble", ["vmap", "array"])
def test_paths_without_lane_tiles_report_none(ensemble):
    ep = _random_rho_lorenz(6)
    r = solve_ensemble_local(ep, alg="tsit5", ensemble=ensemble, t0=0.0,
                             tf=0.5, dt0=1e-3, rtol=1e-5, atol=1e-5)
    assert r.steps_run is None


def test_fixed_xla_scan_reports_none():
    ep = _random_rho_lorenz(6)
    r = solve_ensemble_local(ep, alg="tsit5", ensemble="kernel",
                             backend="xla", t0=0.0, tf=0.5, dt0=1e-2,
                             adaptive=False, n_steps=50, save_every=50)
    assert r.steps_run is None
