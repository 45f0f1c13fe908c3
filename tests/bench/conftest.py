"""Shared fixtures of the benchmark's tests.

The benchmark runs with x64 off, as on the chip; the suite's conftest turns
it on for the program's own tests, so these tests turn it off again."""
import jax
import pytest

# A four-chip cell on the one-chip traffic, for the tests of the harness's
# sharded path: whatever cells BENCHMARK.json holds, this one goes through
# `repro.core.api.solve_ensemble` over the local mesh.
SHARDED = {"name": "sharded_rehearsal", "config": "lorenz_sweep",
           "traffic": "fixed_1000_steps", "chips": 4,
           "why": "the harness's sharded path, in the tests"}


@pytest.fixture
def no_x64():
    with jax.enable_x64(False):
        yield


@pytest.fixture
def with_sharded(monkeypatch):
    """BENCHMARK.json as it is, plus the SHARDED cell."""
    from bench import harness
    spec = harness.load_spec()
    spec["workloads"] = spec["workloads"] + [SHARDED]
    monkeypatch.setattr(harness, "load_spec", lambda root=None: spec)
    return SHARDED["name"]
