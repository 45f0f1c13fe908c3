"""Ahead-of-time compiles of the fused kernels for a TPU v5e, without a chip.

The TPU's kernel compiler (Mosaic) refuses programs that the Pallas
interpreter, which every other test uses, runs without complaint.  These
tests lower the kernels of the main path at the widths `chip_smoke.py` runs
them, in float32 with x64 off as on the chip, and compile them for a
described (not attached) v5e: the erk (fixed and adaptive), sde and
rosenbrock bodies of `run_ensemble_kernel`, the batched LU kernel, and a
data-driven erk solve whose table leaves ride the "table" extras.  Nothing
runs, so they check only that Mosaic accepts each kernel, that it stays
inside the v5e's scoped VMEM limit and that the program holds a TPU kernel
(named for its family, with the seven-row stats block among its outputs).

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library, and xdist workers all import
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.de_problems import (forced_oscillator_problem, gbm_diffusion,
                                       gbm_drift, lorenz_rhs, vdp_rhs)
from repro.core.interp import data_flatten, data_words
from repro.core.methods import get_method
from repro.core.sde import SDE_STEPPERS, sde_save_grid
from repro.kernels.ensemble_kernel import (erk_body, erk_work_words,
                                           rosenbrock_body,
                                           rosenbrock_work_words,
                                           run_ensemble_kernel, sde_body,
                                           sde_work_words)
from repro.kernels.lu.kernel import lu_solve_pallas

F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_v5e(one_chip):
    """compile(fn, *shapes): AOT-compile for one v5e chip with x64 off and
    the persistent cache off (a TPU entry written here could not be read
    back without a chip); returns the compiled program."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        with jax.enable_x64(False):
            compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled

    yield run
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def with_steps(res, field):
    """One output of the solve and its steps_run, so neither is dropped."""
    return getattr(res, field), res.steps_run


def assert_family_kernel(compiled, name):
    """The program's Mosaic call carries the family's name and returns the
    (7, B) int32 stats block, steps_run its last row."""
    calls = [ln.strip() for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    assert any(ln.startswith(f"%{name}.") and "s32[7," in ln
               for ln in calls), calls


@pytest.mark.parametrize("adaptive,N,S", [(False, 2 ** 22, 4),
                                          (True, 2 ** 20, 5)])
def test_erk_body_compiles(compile_v5e, adaptive, N, S):
    tab = get_method("tsit5").tableau
    ts = jnp.linspace(1.0 / S, 1.0, S, dtype=F32)
    body = erk_body(lorenz_rhs, tab, t0=0.0, tf=1.0, dt0=1e-3, rtol=1e-5,
                    atol=1e-5, adaptive=adaptive, max_iters=100_000)
    compiled = compile_v5e(lambda u, p: with_steps(run_ensemble_kernel(
        body, u, p, ts=ts, extras=[("broadcast", ts)],
        work_words=erk_work_words(3, 3, tab.stages), interpret=False), "us"),
        ((N, 3), F32), ((N, 3), F32))
    assert_family_kernel(compiled, "ensemble_erk")


def test_sde_body_compiles(compile_v5e):
    N, n_steps = 2 ** 22, 200
    body = sde_body(gbm_drift, gbm_diffusion, SDE_STEPPERS["em"], "diagonal",
                    t0=0.0, dt=1.0 / n_steps, n_steps=n_steps,
                    save_every=n_steps, m_noise=3, seed=0, use_table=False)
    ts = sde_save_grid(0.0, 1.0 / n_steps, n_steps, n_steps, F32)
    off = jnp.asarray([0], jnp.uint32)
    compiled = compile_v5e(lambda u, p: with_steps(run_ensemble_kernel(
        body, u, p, ts=ts, extras=[("broadcast", off)],
        work_words=sde_work_words(3, 2, 3), interpret=False), "u_final"),
        ((N, 3), F32), ((N, 2), F32))
    assert_family_kernel(compiled, "ensemble_sde")


def test_rosenbrock_body_compiles(compile_v5e):
    N = 2 ** 16
    spec = get_method("rosenbrock23")
    ts = jnp.asarray([1.0], F32)
    body = rosenbrock_body(vdp_rhs, spec.rtableau, t0=0.0, tf=1.0, dt0=1e-2,
                           rtol=1e-5, atol=1e-5, max_iters=100_000,
                           w_reuse=spec.w_reuse)
    compiled = compile_v5e(lambda u, p: with_steps(run_ensemble_kernel(
        body, u, p, ts=ts, extras=[("broadcast", ts)],
        work_words=rosenbrock_work_words(2, 1, stages=spec.rtableau.stages,
                                         w_reuse=bool(spec.w_reuse)),
        interpret=False), "u_final"),
        ((N, 2), F32), ((N, 1), F32))
    assert_family_kernel(compiled, "ensemble_rosenbrock")


def test_lu_kernel_compiles(compile_v5e):
    n, N = 3, 2 ** 16
    compile_v5e(lambda W, b: lu_solve_pallas(W, b, lane_tile=512,
                                             interpret=False),
                ((n, n, N), F32), ((n, N), F32))


def test_table_extras_compile(compile_v5e):
    N = 2 ** 16
    prob = forced_oscillator_problem(dtype=F32)
    tab = get_method("tsit5").tableau
    leaves, _ = data_flatten(prob.data)
    ts = jnp.asarray([5.0], F32)
    body = erk_body(prob.f, tab, t0=0.0, tf=5.0, dt0=1e-2, rtol=1e-5,
                    atol=1e-5, adaptive=True, max_iters=100_000,
                    data=prob.data)
    compiled = compile_v5e(lambda u, p, *lv: with_steps(run_ensemble_kernel(
        body, u, p, ts=ts,
        extras=[("broadcast", ts)] + [("table", leaf) for leaf in lv],
        work_words=erk_work_words(2, 2, tab.stages),
        fixed_words=data_words(prob.data), interpret=False), "u_final"),
        ((N, 2), F32), ((N, 2), F32), *[(leaf.shape, leaf.dtype)
                                         for leaf in leaves])
    assert_family_kernel(compiled, "ensemble_erk")
