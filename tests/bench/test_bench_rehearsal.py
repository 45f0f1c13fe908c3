"""Every cell runs end to end at a small size on the CPU (Pallas
interpreted) and its output checks pass; the command refuses a device that
is not a TPU, and a rehearsal never prints a result line."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in harness.load_spec()["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_rehearses_correct(workload, no_x64):
    r = harness.run_cell(workload, 2 ** 31 + 11, 0.05, False,
                         t_process=time.perf_counter(), rehearse_n=256)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"traj_per_s", "setup_s"}
    assert r["metrics"]["traj_per_s"]["value"] > 0
    assert r["device"]["count"] >= 1


def _cli(*args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("JAX_ENABLE_X64", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=600)


def test_cli_refuses_a_cpu():
    p = _cli("--workload", "lorenz_fixed", "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no CPU fallback" in p.stderr


def test_cli_rehearsal_exits_nonzero_without_a_result():
    p = _cli("--workload", CELLS[0], "--seed", "5", "--seconds", "0.01",
             "--trace", "0", "--rehearse", "128")
    assert p.returncode == 3 and p.stdout.strip() == "", p.stderr[-2000:]
    line = [ln for ln in p.stderr.splitlines() if ln.startswith("rehearsal ")]
    assert json.loads(line[-1][len("rehearsal "):])["correct"] is True


SHARDED_RUN = """
import json, sys, time
sys.path[:0] = [".", "src"]
from bench import harness
sys.path.insert(0, "tests/bench")
from conftest import SHARDED
spec = harness.load_spec()
spec["workloads"].append(SHARDED)
harness.load_spec = lambda root=None: spec
r = harness.run_cell(SHARDED["name"], 5, 0.01, False,
                     t_process=time.perf_counter(), rehearse_n=512)
print(json.dumps(r))
"""


def test_sharded_path_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_ENABLE_X64", None)
    p = subprocess.run([sys.executable, "-c", SHARDED_RUN], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["device"]["count"] == 4


def test_compile_counter_sees_a_compile(no_x64):
    import jax
    import jax.numpy as jnp
    with harness.CompileCounter() as counter:
        jax.jit(lambda x: x * 3.0 + 1.0)(jnp.arange(7.0)).block_until_ready()
    assert counter.count >= 1
    with harness.CompileCounter() as quiet:
        jnp.arange(7.0).block_until_ready()
    assert quiet.count == 0


def test_key_words_take_any_whole_seed():
    words = [tuple(harness.key_words(s)) for s in (0, 1, 2 ** 31 + 5,
                                                   2 ** 40, -3)]
    assert len(set(words)) == len(words)
    assert tuple(harness.key_words(2 ** 31 + 5)) == words[2]
