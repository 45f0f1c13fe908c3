"""Dataset tables as a dispatch capability (§6.7 tentpole).

`ODEProblem.data` / `SDEProblem.data` carry a pytree of UniformTable1D/2D
leaves through EVERY dispatch path.  Contracts proven here:

  * fixed-dt parity is exact across {vmap, array, kernel} x {xla, pallas}
    for a data-driven RHS (same step sequence everywhere — only the data
    plumbing differs);
  * adaptive parity holds at the kink-limited tolerance: a piecewise-linear
    forcing is only C0 at knots, so the embedded estimator cannot see the
    local error there and ULP-level fusion differences may legitimately
    shift accept/reject decisions — paths agree to ~the true kink error,
    not to roundoff;
  * sharded == local bitwise (tables BROADCAST as replicated shard_map
    inputs, never sharded);
  * `jax.grad` w.r.t. TABLE VALUES agrees across vmap/kernel-xla/
    kernel-pallas and with central finite differences (f64, <=1e-4) —
    the forced-oscillator calibration loop of the acceptance bar;
  * SDE drift/diffusion tables replay bitwise across strategies (pathwise
    counter-RNG noise is data-independent);
  * events compose with data on every path;
  * a method declaring ``data_rhs=False`` is rejected by `valid_dispatch`
    and by the front door;
  * the autotune key grows a dataset-shape component, so data-driven and
    data-free solves of the same method never share a profile entry.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (EnsembleProblem, ODEProblem, SDEProblem,
                        UniformTable1D, bind_problem_data, get_method,
                        interp1d, solve_ensemble_local, valid_dispatch)
from repro.core.events import Event
from repro.configs.de_problems import forced_oscillator_problem

ALL_PATHS = [("vmap", "xla"), ("array", "xla"),
             ("kernel", "xla"), ("kernel", "pallas")]
GRAD_PATHS = [("vmap", "xla"), ("kernel", "xla"), ("kernel", "pallas")]


def osc_ens(N=8, dtype=jnp.float64):
    prob = forced_oscillator_problem(dtype=dtype)
    u0s = jnp.stack([prob.u0] * N) * jnp.linspace(
        0.5, 1.5, N, dtype=dtype)[:, None]
    ps = jnp.stack([prob.p] * N)
    return prob, EnsembleProblem(prob, N, u0s=u0s, ps=ps)


# ---------------------------------------------------------------------------
# parity bar
# ---------------------------------------------------------------------------

def test_fixed_dt_parity_all_paths():
    _, ep = osc_ens()
    res = {}
    for strat, backend in ALL_PATHS:
        r = solve_ensemble_local(ep, alg="tsit5", ensemble=strat,
                                 backend=backend, adaptive=False, dt0=0.01,
                                 saveat=jnp.linspace(1.0, 5.0, 5))
        res[(strat, backend)] = (np.asarray(r.us), np.asarray(r.u_final))
    us0, uf0 = res[("vmap", "xla")]
    for k, (us, uf) in res.items():
        np.testing.assert_allclose(us, us0, atol=1e-12, err_msg=str(k))
        np.testing.assert_allclose(uf, uf0, atol=1e-12, err_msg=str(k))


def test_adaptive_parity_kink_limited():
    _, ep = osc_ens()
    kw = dict(alg="tsit5", saveat=jnp.linspace(0.0, 5.0, 11), dt0=1e-2,
              rtol=1e-8, atol=1e-8)
    ref = solve_ensemble_local(ep, ensemble="vmap", backend="xla", **kw)
    for strat, backend in ALL_PATHS[1:]:
        r = solve_ensemble_local(ep, ensemble=strat, backend=backend, **kw)
        np.testing.assert_allclose(np.asarray(r.u_final),
                                   np.asarray(ref.u_final), atol=2e-5,
                                   err_msg=f"{strat}/{backend}")
    # within the kernel family the two backends ARE bitwise twins
    rx = solve_ensemble_local(ep, ensemble="kernel", backend="xla", **kw)
    rp = solve_ensemble_local(ep, ensemble="kernel", backend="pallas", **kw)
    np.testing.assert_allclose(np.asarray(rp.u_final),
                               np.asarray(rx.u_final), atol=1e-12)


def test_gather_onehot_modes_agree_in_kernel():
    prob, _ = osc_ens()
    tab = prob.data["force"]
    N = 4
    u0s = jnp.stack([prob.u0] * N)
    ps = jnp.stack([prob.p] * N)
    out = {}
    for mode in ("gather", "onehot"):
        def rhs(u, p, t, data, _m=mode):
            return jnp.stack([u[1], -p[0] * u[0] - p[1] * u[1]
                              + interp1d(data["force"], t, _m)])
        pm = dataclasses.replace(prob, f=rhs)
        ep = EnsembleProblem(pm, N, u0s=u0s, ps=ps)
        r = solve_ensemble_local(ep, alg="tsit5", ensemble="kernel",
                                 backend="pallas", adaptive=False, dt0=0.01,
                                 n_steps=200, save_every=200)
        out[mode] = np.asarray(r.u_final)
    np.testing.assert_allclose(out["gather"], out["onehot"], atol=1e-12)


def test_rosenbrock_data_parity():
    def stiff_rhs(u, p, t, data):
        return jnp.stack([u[1], -p[0] * u[0] - p[1] * u[1]
                          + interp1d(data["force"], t)])
    base = forced_oscillator_problem()
    prob = dataclasses.replace(base, f=stiff_rhs,
                               p=jnp.asarray([50.0, 2.0], jnp.float64),
                               tspan=(0.0, 3.0))
    N = 6
    u0s = jnp.stack([prob.u0] * N) * jnp.linspace(0.5, 1.5, N)[:, None]
    ps = jnp.stack([prob.p] * N)
    ep = EnsembleProblem(prob, N, u0s=u0s, ps=ps)
    kw = dict(alg="rosenbrock23", saveat=jnp.linspace(0.0, 3.0, 7), dt0=1e-3,
              rtol=1e-8, atol=1e-8)
    ref = solve_ensemble_local(ep, ensemble="vmap", backend="xla", **kw)
    for strat, backend in ALL_PATHS[1:]:
        r = solve_ensemble_local(ep, ensemble=strat, backend=backend, **kw)
        np.testing.assert_allclose(np.asarray(r.u_final),
                                   np.asarray(ref.u_final), atol=2e-5,
                                   err_msg=f"{strat}/{backend}")


def test_sde_data_bitwise_parity():
    ts = np.linspace(0.0, 2.0, 33)
    rate = UniformTable1D(jnp.asarray(0.02 + 0.01 * np.sin(ts)), 0.0,
                          float(ts[1] - ts[0]))

    def drift(u, p, t, d):
        return interp1d(d["rate"], t) * u

    def diffusion(u, p, t, d):
        return p[0] * u

    prob = SDEProblem(f=drift, g=diffusion, u0=jnp.ones(1),
                      p=jnp.asarray([0.2]), tspan=(0.0, 1.0),
                      noise="diagonal", data={"rate": rate})
    N = 8
    ep = EnsembleProblem(prob, N, u0s=jnp.ones((N, 1)),
                         ps=jnp.full((N, 1), 0.2))
    kw = dict(alg="em", dt0=1e-3, n_steps=500, save_every=250, seed=7)
    ref = solve_ensemble_local(ep, ensemble="vmap", backend="xla", **kw)
    for strat, backend in ALL_PATHS[1:]:
        r = solve_ensemble_local(ep, ensemble=strat, backend=backend, **kw)
        np.testing.assert_allclose(np.asarray(r.u_final),
                                   np.asarray(ref.u_final), atol=1e-14,
                                   err_msg=f"{strat}/{backend}")
    # adaptive SDE engine sees the dataset too
    ra = solve_ensemble_local(ep, ensemble="kernel", backend="pallas",
                              alg="em", adaptive=True, dt0=1e-3,
                              saveat=jnp.linspace(0.0, 1.0, 5), rtol=1e-4,
                              atol=1e-6, seed=7)
    rv = solve_ensemble_local(ep, ensemble="vmap", backend="xla", alg="em",
                              adaptive=True, dt0=1e-3,
                              saveat=jnp.linspace(0.0, 1.0, 5), rtol=1e-4,
                              atol=1e-6, seed=7)
    np.testing.assert_allclose(np.asarray(ra.u_final),
                               np.asarray(rv.u_final), atol=1e-12)


def test_events_compose_with_data():
    def rhs(u, p, t, data):
        return jnp.stack([u[1], -p[0] * u[0] + interp1d(data["force"], t)])
    base = forced_oscillator_problem()
    prob = dataclasses.replace(base, f=rhs, u0=jnp.asarray([0.0, 2.0]),
                               p=jnp.asarray([1.0, 0.0]))
    N = 4
    u0s = jnp.stack([prob.u0] * N) * jnp.linspace(0.8, 1.2, N)[:, None]
    ps = jnp.stack([prob.p] * N)
    ep = EnsembleProblem(prob, N, u0s=u0s, ps=ps)
    ev = Event(condition=lambda u, p, t: u[0] - 1.5, direction=1,
               terminal=True)
    kw = dict(alg="tsit5", saveat=jnp.linspace(0.0, 5.0, 6), dt0=1e-2,
              rtol=1e-8, atol=1e-8, event=ev)
    ref = solve_ensemble_local(ep, ensemble="vmap", backend="xla", **kw)
    for strat, backend in (("kernel", "xla"), ("kernel", "pallas")):
        r = solve_ensemble_local(ep, ensemble=strat, backend=backend, **kw)
        np.testing.assert_allclose(np.asarray(r.t_final),
                                   np.asarray(ref.t_final), atol=1e-9,
                                   err_msg=f"{strat}/{backend}")


# ---------------------------------------------------------------------------
# sharded == local
# ---------------------------------------------------------------------------

def test_sharded_equals_local_with_data():
    from jax.sharding import Mesh
    from repro.core.api import solve_ensemble
    _, ep = osc_ens()
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    kw = dict(alg="tsit5", saveat=jnp.linspace(0.0, 5.0, 6), dt0=1e-2,
              rtol=1e-7, atol=1e-7, ensemble="kernel", backend="pallas")
    rl = solve_ensemble_local(ep, **kw)
    rm = solve_ensemble(ep, mesh=mesh, **kw)
    np.testing.assert_array_equal(np.asarray(rl.u_final),
                                  np.asarray(rm.u_final))
    np.testing.assert_array_equal(np.asarray(rl.us), np.asarray(rm.us))


# ---------------------------------------------------------------------------
# gradients reach table values (the calibration acceptance bar)
# ---------------------------------------------------------------------------

def test_grad_wrt_table_values_matches_fd_all_paths():
    prob, ep = osc_ens()
    tab = prob.data["force"]
    N = ep.n_trajectories
    u0s, ps = ep.materialize()
    kw = dict(alg="tsit5", adaptive=False, dt0=0.01,
              saveat=jnp.linspace(1.0, 5.0, 5))

    def L(vals, ensemble, backend):
        p2 = dataclasses.replace(
            prob, data={"force": UniformTable1D(vals, tab.x0, tab.dx)})
        ep2 = EnsembleProblem(p2, N, u0s=u0s, ps=ps)
        r = solve_ensemble_local(ep2, ensemble=ensemble, backend=backend,
                                 sensitivity="adjoint", adjoint_steps=520,
                                 **kw)
        return jnp.sum(r.u_final ** 2) + jnp.sum(r.us ** 2)

    v0 = tab.values
    grads = {sb: np.asarray(jax.grad(lambda v: L(v, *sb))(v0))
             for sb in GRAD_PATHS}
    g0 = grads[("vmap", "xla")]
    for sb, g in grads.items():
        np.testing.assert_allclose(g, g0, atol=1e-10, err_msg=str(sb))

    # central FD on both required backends (f64, rel <= 1e-4)
    h = 1e-6
    for backend in ("xla", "pallas"):
        sb = ("vmap", "xla") if backend == "xla" else ("kernel", "pallas")
        g = grads[sb]
        for i in (int(np.argmax(np.abs(g))), 5, 20):
            e = jnp.zeros_like(v0).at[i].set(h)
            fd = (float(L(v0 + e, *sb)) - float(L(v0 - e, *sb))) / (2 * h)
            np.testing.assert_allclose(float(g[i]), fd, rtol=1e-4,
                                       err_msg=f"{sb} i={i}")


# ---------------------------------------------------------------------------
# capability flag + autotune key
# ---------------------------------------------------------------------------

def test_valid_dispatch_rejects_data_incapable_method():
    spec = get_method("tsit5")
    assert valid_dispatch(spec, "vmap", "xla", data=True)[0]
    nodata = dataclasses.replace(spec, name="nodata", data_rhs=False)
    ok, why = valid_dispatch(nodata, "vmap", "xla", data=True)
    assert not ok and "data_rhs" in why
    # without data the same method stays dispatchable
    assert valid_dispatch(nodata, "vmap", "xla", data=False)[0]


def test_front_door_rejects_data_incapable_method():
    prob, ep = osc_ens(N=2)
    spec = dataclasses.replace(get_method("tsit5"), name="nodata_tsit5",
                               data_rhs=False)
    with pytest.raises(ValueError, match="data_rhs"):
        solve_ensemble_local(ep, alg=spec, ensemble="vmap",
                             saveat=jnp.asarray([5.0]), dt0=1e-2)


def test_bind_problem_data_closes_over_tables():
    prob, _ = osc_ens(N=2)
    bound = bind_problem_data(prob)
    assert bound.data is None
    u = jnp.asarray([1.0, 0.0])
    want = prob.f(u, prob.p, 0.37, prob.data)
    np.testing.assert_allclose(np.asarray(bound.f(u, prob.p, 0.37)),
                               np.asarray(want), atol=0)


def test_autotune_key_has_data_component():
    from repro.core.autotune import config_key
    from repro.core.interp import data_signature
    prob, _ = osc_ens(N=2)
    spec = get_method("tsit5")
    kw = dict(n=2, N=8, dtype=jnp.float64, adaptive=True, events=False,
              w_reuse=False, error_est="none")
    k_free = config_key(spec, **kw)
    k_data = config_key(spec, data_sig=data_signature(prob.data), **kw)
    assert "data=none" in k_free
    assert "data=" in k_data and k_free != k_data
    # signature tracks shape AND dtype, so retuning triggers on either
    assert data_signature(prob.data) != "none"


def test_resolve_auto_key_distinguishes_data(tmp_path):
    from repro.core.autotune import clear_memory_cache, resolve_auto
    prob, ep = osc_ens(N=4)
    clear_memory_cache()
    cache = str(tmp_path / "tune.json")
    spec = get_method("tsit5")
    dec_data = resolve_auto(ep, spec, dt0=1e-2,
                            saveat=jnp.linspace(0.0, 5.0, 6),
                            cache_path=cache, repeats=1)
    free = EnsembleProblem(
        dataclasses.replace(bind_problem_data(prob), name="free"),
        4, u0s=ep.materialize()[0], ps=ep.materialize()[1])
    dec_free = resolve_auto(free, spec, dt0=1e-2,
                            saveat=jnp.linspace(0.0, 5.0, 6),
                            cache_path=cache, repeats=1)
    assert dec_data.key != dec_free.key
    assert "data=" in dec_data.key
    # the bound twin's RHS closes over the table arrays, which pallas_call
    # refuses as captured constants: its Pallas candidates are pruned (not
    # timed and not raised), while the data problem — tables passed as
    # kernel inputs — keeps them
    assert dec_data.source == dec_free.source == "tuned"
    assert any("pallas" in label for label, _ in dec_data.timings)
    assert not any("pallas" in label for label, _ in dec_free.timings)
    assert dec_free.backend != "pallas"


@pytest.mark.parametrize("mode", ["gather", "cubic"])
def test_take1d_gathers_outside_kernels_masks_inside(mode):
    """A 1-D lookup is an XLA gather (O(1) per lookup) on the vmap and XLA
    paths, and a masked sum over the K nodes only while a Pallas kernel
    body traces, where Mosaic has no 1-D gather.  Both give the same
    values bitwise."""
    from repro.core import interp
    tab = UniformTable1D(
        jnp.asarray(np.random.default_rng(0).normal(size=65)), 0.0, 0.1)
    x = jnp.linspace(-1.0, 8.0, 37)

    def prims():     # jnp.take nests its gather in a jit: print it all
        return str(jax.make_jaxpr(lambda q: interp1d(tab, q, mode))(x))

    outside, y_out = prims(), interp1d(tab, x, mode)
    with interp.kernel_lookups():
        inside, y_in = prims(), interp1d(tab, x, mode)
    assert "gather" in outside and "gather" not in inside
    np.testing.assert_array_equal(np.asarray(y_in), np.asarray(y_out))
