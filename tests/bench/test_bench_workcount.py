"""The configurations' operation counts, stated as sums of commented terms,
equal a primitive count of the jaxpr of the plain jax.numpy step written
beside them from the equations and the tableau (bench/workcount.py)."""
import jax.numpy as jnp
import pytest

from bench import harness, peaks
from bench.configs import gbm_mc, lorenz_sweep
from bench.workcount import count_ops
from repro.kernels.ensemble_kernel import N_STATS

F32 = jnp.float32
U = jnp.asarray([1.0, 0.5, 2.0], F32)
P = (F32(10.0), F32(20.0), F32(8.0 / 3.0))
S = F32(0.01)
K1 = lorenz_sweep.rhs(U, P)


def _lorenz_rhs():
    return count_ops(lambda u: lorenz_sweep.rhs(u, P), U)


def _lorenz_fixed():
    return count_ops(lambda u, t, k: lorenz_sweep.plain_fixed_step(
        u, P, t, S, k), U, S, K1)


def _lorenz_adaptive():
    return count_ops(lambda u, t, dt, k, ep: lorenz_sweep.plain_adaptive_attempt(
        u, P, t, dt, k, ep, 1.0, 1e-5, 1e-5), U, S, S, K1, S)


def _lorenz_save():
    ks = [K1] * 7
    return count_ops(lambda u, h, t, ts: lorenz_sweep.plain_dense_save(
        u, h, t, ts, ks), U, S, S, S)


def _gbm_step():
    return count_ops(lambda u, k, lane: gbm_mc.plain_em_step(
        u, k, lane, 1.5, 0.01, 0.005), U, jnp.uint32(3), jnp.uint32(7))


@pytest.mark.parametrize("counted, stated", [
    (_lorenz_rhs, lorenz_sweep.OPS_RHS),
    (_lorenz_fixed, lorenz_sweep.OPS_FIXED_STEP),
    (_lorenz_adaptive, lorenz_sweep.OPS_ADAPTIVE_ATTEMPT),
    (_lorenz_save, lorenz_sweep.OPS_DENSE_SAVE),
    (_gbm_step, gbm_mc.OPS_EM_STEP),
], ids=["lorenz_rhs", "tsit5_fixed_step", "tsit5_adaptive_attempt",
        "tsit5_dense_save", "em_threefry_step"])
def test_stated_count_matches_jaxpr(counted, stated, no_x64):
    assert counted() == stated


def test_known_totals():
    # 9 per RHS; 6 RHS + 144 stage arithmetic + t += dt
    assert lorenz_sweep.OPS_FIXED_STEP == 199
    # 3 normals of 125 operations, 7 per state of EM, one shared product
    assert gbm_mc.OPS_EM_STEP == 397


def test_work_uses_the_stepping_of_the_traffic():
    fixed = lorenz_sweep.work({"adaptive": False, "dt": 1e-3,
                               "n_steps": 1000, "save_every": 250})
    assert fixed["ops_per_attempt"] == lorenz_sweep.OPS_FIXED_STEP
    assert fixed["ops_per_save"] == 0 and fixed["saves"] == 4
    adaptive = lorenz_sweep.work({"adaptive": True,
                                  "saveat": [0.2, 0.4, 0.6, 0.8, 1.0]})
    assert adaptive["ops_per_attempt"] == lorenz_sweep.OPS_ADAPTIVE_ATTEMPT
    assert adaptive["ops_per_save"] == lorenz_sweep.OPS_DENSE_SAVE
    assert adaptive["saves"] == 5


def test_peak_microkernel_count():
    # x = x * a + b on every element of an (8, 128) tile: 2 operations
    assert peaks.ops(chains=16, unroll=4, iters=10) == 16 * 4 * 10 * 2 * 1024


@pytest.mark.parametrize("config, traffic, fixed_words", [
    (lorenz_sweep, "fixed_1000_steps", 3 + 3 + 3 + 1),
    (lorenz_sweep, "adaptive_tol_1e-5", 3 + 3 + 3 + 1),
    (gbm_mc, "em_200_steps", 3 + 2 + 3 + 1),
])
def test_bytes_count_every_stats_row_the_kernel_writes(config, traffic,
                                                       fixed_words):
    # state and parameters read; final state and time, each save's state
    # and every row of the kernel's int32 stats block written
    w = config.work(harness.load_traffic(traffic))
    assert w["bytes_per_traj"] == 4 * (fixed_words + 3 * w["saves"] + N_STATS)
