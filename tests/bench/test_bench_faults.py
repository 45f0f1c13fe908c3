"""The checks refuse a broken timed path and the lower-precision control.

Each test drives a whole run of a cell at a small size on the CPU (Pallas
interpreted), skipping only the harness's look for a chip, with the timed
solve broken underneath, and sees `correct` come out false.  The faults
each cell can have: a step that returns its state unchanged, half of the
ensemble left out, one block of answers altered where they are produced
(each takes its neighbour's), and on the sharded cell the exchange between
chips left out (every shard returns the first shard's answers).  The
control, the configuration's plain method in bfloat16 in the program's
place, has to fail a check of every cell too."""
import time

import jax.numpy as jnp
import pytest

from bench import control, harness
from bench.checks import SAMPLE_BLOCK

N = 256
STATE = ("us", "u_final")


def _map_state(solve, fn):
    def broken(*args):
        out = solve(*args)
        return {k: (fn(v, args) if k in STATE else v) for k, v in out.items()}
    return broken


def stale_state(solve, n):
    def unchanged(v, args):
        u0s = args[0]
        return jnp.broadcast_to(u0s.reshape(u0s.shape[:1] + (1,) * (v.ndim - 2)
                                            + u0s.shape[1:]), v.shape)
    return _map_state(solve, unchanged)


def half_batch(solve, n):
    return _map_state(solve, lambda v, a: v.at[n // 2:].set(0.0))


def altered(solve, n):
    lo, hi = n // 2, min(n, n // 2 + SAMPLE_BLOCK)
    return _map_state(solve, lambda v, a: v.at[lo:hi].set(
        jnp.roll(v[lo:hi], 1, axis=0)))


def no_exchange(solve, n, shards=4):
    return _map_state(solve, lambda v, a: jnp.concatenate(
        [v[:n // shards]] * shards))


SPEC = harness.load_spec()
FAULTS = [(w["name"], f) for w in SPEC["workloads"]
          for f in (stale_state, half_batch, altered)
          + ((no_exchange,) if w["chips"] > 1 else ())]


def _run(workload, fault=None, seed=12345):
    return harness.run_cell(workload, seed, 0.01, False,
                            t_process=time.perf_counter(), rehearse_n=N,
                            fault=fault)


@pytest.mark.parametrize("workload, fault", FAULTS,
                         ids=[f"{w}-{f.__name__}" for w, f in FAULTS])
def test_fault_is_refused(workload, fault, no_x64):
    result = _run(workload, fault)
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] >= 1
    assert any(c["value"] > c["limit"] for c in result["checks"].values())


def test_sharded_path_without_exchange_is_refused(with_sharded, no_x64):
    result = _run(with_sharded, no_exchange)
    assert result["correct"] is False


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]
                                      if w["chips"] == 1])
def test_control_is_refused(workload, no_x64):
    (seed, nums), = control.readings(workload, [7], "control", N)
    assert any(v > lim for v, lim in nums.values()), nums
