"""Stiff subsystem: s-stage W-method engine, Rodas tableaus, pivoted LU,
analytic-Jacobian hook, and the ROBER cross-strategy/backend parity bar.

ROBER's rate constants span ~9 orders of magnitude, so everything here is
float64 (conftest enables jax_enable_x64; CI additionally runs this file in a
dedicated x64 leg)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.de_problems import (orego_problem, rober_ensemble,
                                       rober_jac, rober_problem, rober_rhs)
from repro.core import (EnsembleProblem, get_method, initial_dt,
                        solve_ensemble_local)
from repro.core.order_conditions import (max_rosenbrock_condition_residual,
                                         rosenbrock_consistency_residual)
from repro.core.rosenbrock import rosenbrock_step, solve_rosenbrock
from repro.core.tableaus import RODAS4, RODAS5P, ROS23W, RosenbrockTableau

RB_TABS = [ROS23W, RODAS4, RODAS5P]


# ---------------------------------------------------------------------------
# tableau verification: algebraic order conditions + empirical convergence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rtab", RB_TABS, ids=lambda t: t.name)
def test_rosenbrock_order_conditions(rtab):
    # propagated weights satisfy every rooted-tree condition of the claimed
    # order; the first condition of order+1 fails (the order is sharp)
    assert max_rosenbrock_condition_residual(rtab, rtab.order) < 1e-12
    assert max_rosenbrock_condition_residual(rtab, rtab.order + 1) > 1e-4
    # embedded weights hold their claimed order
    assert max_rosenbrock_condition_residual(
        rtab, rtab.embedded_order, embedded=True) < 1e-12
    # c = rowsum(alpha), d = rowsum(Gamma): non-autonomous consistency
    assert rosenbrock_consistency_residual(rtab) < 1e-12


@pytest.mark.parametrize("rtab,expected", [(ROS23W, 2), (RODAS4, 4),
                                           (RODAS5P, 5)],
                         ids=lambda v: getattr(v, "name", v))
def test_rosenbrock_empirical_convergence(rtab, expected):
    # u' = lam*(u - sin t) + cos t, u(0)=0  =>  u = sin t: non-autonomous
    # (exercises the c/d data), stiff-ish lam, known solution.
    p = jnp.asarray([-5.0])

    def f(u, p_, t):
        return p_[0] * (u - jnp.sin(t)) + jnp.cos(t)

    def endpoint_err(n):
        u = jnp.asarray([0.0])
        t = jnp.asarray(0.0)
        dt = jnp.asarray(1.5 / n)
        for _ in range(n):
            u, _, _, _, _ = rosenbrock_step(f, rtab, u, p, t, dt)
            t = t + dt
        return abs(float(u[0]) - np.sin(1.5))

    errs = [endpoint_err(n) for n in (20, 40, 80)]
    slopes = [np.log2(errs[i] / errs[i + 1]) for i in range(2)]
    assert min(slopes) > expected - 0.35, (errs, slopes)


def test_rodas4_dense_output_is_third_order():
    # the stiffly-accurate interp_h weights: interpolated mid-step values
    # converge one order above cubic-accurate (O(h^4) local error)
    p = jnp.asarray([-5.0])

    def f(u, p_, t):
        return p_[0] * (u - jnp.sin(t)) + jnp.cos(t)

    def interp_err(h):
        u = jnp.asarray([np.sin(0.4)])
        t = jnp.asarray(0.4)
        u1, _, _, _, kds = rosenbrock_step(f, RODAS4, u, p, t, jnp.asarray(h))
        errs = []
        for th in (0.3, 0.5, 0.7):
            ui = (1 - th) * u + th * (u1 + (1 - th) * (kds[0] + th * kds[1]))
            errs.append(abs(float(ui[0]) - np.sin(0.4 + th * h)))
        return max(errs)

    e1, e2 = interp_err(0.2), interp_err(0.1)
    assert np.log2(e1 / e2) > 3.3, (e1, e2)


def test_registry_has_rodas_methods():
    for name, order in (("rodas4", 4), ("rodas5p", 5)):
        spec = get_method(name)
        assert spec.family == "rosenbrock" and spec.stiff
        assert spec.order == order and spec.rtableau is not None
    assert get_method("gpurodas4") is get_method("rodas4")
    assert get_method("rodas5") is get_method("rodas5p")
    assert get_method("gpurosenbrock23") is get_method("ode23s")
    # a bare RosenbrockTableau is auto-wrapped like a bare Butcher Tableau
    spec = get_method(RODAS4)
    assert spec.family == "rosenbrock" and spec.rtableau is RODAS4
    # family capability validation
    with pytest.raises(ValueError, match="rtableau"):
        from repro.core import MethodSpec
        MethodSpec(name="bad_rb", family="rosenbrock", order=3)
    # a tableau without embedded weights cannot drive the adaptive engine:
    # rejected loudly, not silently integrated with err == 0
    no_pair = RODAS4._replace(name="rodas4_nopair",
                              btilde=np.zeros_like(RODAS4.btilde))
    assert not get_method(no_pair).adaptive
    ens = rober_ensemble(2, tspan=(0.0, 1.0))
    with pytest.raises(ValueError, match="btilde"):
        solve_ensemble_local(ens, alg=no_pair, ensemble="vmap", dt0=1e-6)


# ---------------------------------------------------------------------------
# ROBER: the acceptance bar — every strategy/backend matches the jnp
# reference solve (vmap + LAPACK linsolve) to rtol 1e-6 in f64
# ---------------------------------------------------------------------------

ROBER_SAVEAT = jnp.asarray([1e-2, 1.0, 1e2, 1e4])


def _rober_solve(alg, ensemble, backend, linsolve="jnp", analytic_jac=True,
                 w_reuse=None):
    ens = rober_ensemble(3, tspan=(0.0, 1e4), analytic_jac=analytic_jac)
    return solve_ensemble_local(ens, alg=alg, ensemble=ensemble,
                                backend=backend, dt0=1e-6, rtol=1e-8,
                                atol=1e-10, saveat=ROBER_SAVEAT,
                                linsolve=linsolve, w_reuse=w_reuse)


@pytest.mark.parametrize("w_reuse", [None, True],
                         ids=["eager", "lazy-W"])
@pytest.mark.parametrize("alg", ["rodas4", "rodas5p"])
@pytest.mark.parametrize("ensemble,backend,linsolve", [
    ("vmap", "xla", "jnp"),
    ("array", "xla", "jnp"),
    ("array", "xla", "pallas"),      # batched-LU Pallas kernel launch
    ("kernel", "xla", "jnp"),
    ("kernel", "pallas", "jnp"),     # fused kernel: LU body inlined ("lanes")
])
def test_rober_cross_strategy_backend_parity(alg, ensemble, backend, linsolve,
                                             w_reuse):
    # the SAME parity bar with the lazy-W hot path on: the WReusePolicy is a
    # pure function of per-lane quantities, so reuse-on trajectories agree
    # across every strategy/backend/linsolver like reuse-off ones
    ref = _rober_solve(alg, "vmap", "xla", w_reuse=w_reuse)  # jnp reference
    res = _rober_solve(alg, ensemble, backend, linsolve, w_reuse=w_reuse)
    assert int(res.status) == 0
    for got, want in ((res.us, ref.us), (res.u_final, ref.u_final)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-14)
    # y1 + y2 + y3 is conserved by ROBER; 1e-8-tolerance solves hold it tight
    totals = np.asarray(res.u_final).sum(axis=1)
    np.testing.assert_allclose(totals, 1.0, rtol=1e-7)


# ---------------------------------------------------------------------------
# lazy-W hot path: njac/nfact accounting and the reuse win (ISSUE 5 tentpole)
# ---------------------------------------------------------------------------

def _rober_reuse_solve(backend, w_reuse, rtol=1e-6):
    ens = rober_ensemble(4, tspan=(0.0, 1e4))
    return solve_ensemble_local(ens, alg="rosenbrock23", ensemble="kernel",
                                backend=backend, dt0=1e-6, rtol=rtol,
                                atol=rtol * 1e-2, w_reuse=w_reuse)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_w_reuse_cuts_njac_at_matched_accuracy(backend):
    """The acceptance regression: ROBER ensemble at rtol 1e-6, reuse on must
    cut njac >= 2x versus reuse off at indistinguishable accuracy — on the
    XLA lanes path AND the fused Pallas kernel (interpret on CPU)."""
    ens = rober_ensemble(4, tspan=(0.0, 1e4))
    ref = solve_ensemble_local(ens, alg="rodas5p", ensemble="vmap",
                               backend="xla", dt0=1e-6, rtol=1e-10,
                               atol=1e-12).u_final
    scale = np.abs(np.asarray(ref)) + 1e-30
    off = _rober_reuse_solve(backend, False)
    on = _rober_reuse_solve(backend, True)
    assert int(off.status) == 0 and int(on.status) == 0
    # >= 2x fewer Jacobian evaluations (measured: ~10x with the secant-update
    # policy; the bar is deliberately conservative)
    assert int(off.njac) >= 2 * int(on.njac), (int(off.njac), int(on.njac))
    # ... at indistinguishable accuracy: both solves sit at the tolerance's
    # error level, within a small factor of each other
    e_off = np.max(np.abs(np.asarray(off.u_final) - ref) / scale)
    e_on = np.max(np.abs(np.asarray(on.u_final) - ref) / scale)
    assert e_on < 10 * max(e_off, 1e-7), (e_on, e_off)
    # the reuse also wins the combined rhs+jac work metric (nf + n*njac)
    n = 3
    work_off = int(off.nf) + n * int(off.njac)
    work_on = int(on.nf) + n * int(on.njac)
    assert work_off >= 1.3 * work_on, (work_off, work_on)


def test_w_reuse_off_is_eager_every_step():
    """Reuse off must reproduce today's every-step behaviour: one Jacobian
    evaluation and one factorization per ATTEMPTED step, observable through
    the new work counters."""
    off = _rober_reuse_solve("xla", False)
    steps = int(np.sum(np.asarray(off.naccept) + np.asarray(off.nreject)))
    assert int(off.njac) == steps
    assert int(off.nfact) == steps
    # and w_reuse=False is the registered default (spec.w_reuse False)
    default = _rober_reuse_solve("xla", None)
    assert int(default.njac) == int(off.njac)
    np.testing.assert_array_equal(np.asarray(default.u_final),
                                  np.asarray(off.u_final))


def test_w_reuse_policy_knobs_and_frozen_mode():
    """A custom WReusePolicy threads through; secant=0 (frozen-J mode with
    dt-blame retries) still converges and still saves Jacobian work."""
    from repro.core import WReusePolicy
    ens = rober_ensemble(2, tspan=(0.0, 1e3))
    kw = dict(alg="rosenbrock23", ensemble="kernel", backend="xla", dt0=1e-6,
              rtol=1e-6, atol=1e-8)
    off = solve_ensemble_local(ens, w_reuse=False, **kw)
    frozen = solve_ensemble_local(
        ens, w_reuse=WReusePolicy(secant=0.0, max_age=10), **kw)
    assert int(frozen.status) == 0
    assert int(frozen.njac) < int(off.njac)
    # stats flow through vmap dispatch too (scalar-mode engine)
    on_v = solve_ensemble_local(ens, ensemble="vmap", alg="rosenbrock23",
                                backend="xla", dt0=1e-6, rtol=1e-6,
                                atol=1e-8, w_reuse=True)
    assert int(on_v.status) == 0 and int(on_v.njac) > 0
    # non-stiff families reject a truthy knob loudly ...
    from repro.configs.de_problems import rober_problem
    from repro.core import EnsembleProblem
    with pytest.raises(ValueError, match="w_reuse"):
        solve_ensemble_local(EnsembleProblem(rober_problem(), 2), alg="tsit5",
                             w_reuse=True)
    # ... but w_reuse=False stays the documented universal no-op, so generic
    # A/B sweeps can pass it to every method
    res = solve_ensemble_local(EnsembleProblem(rober_problem(), 2),
                               alg="tsit5", tf=1.0, dt0=1e-3, w_reuse=False)
    assert int(res.status) == 0


def test_rober_analytic_jac_matches_jacfwd():
    # the hook changes HOW J is computed, not its value: identical solves
    res_an = _rober_solve("rodas4", "kernel", "xla", analytic_jac=True)
    res_ad = _rober_solve("rodas4", "kernel", "xla", analytic_jac=False)
    np.testing.assert_allclose(np.asarray(res_an.u_final),
                               np.asarray(res_ad.u_final), rtol=1e-12)
    u = jnp.asarray([0.7, 2e-5, 0.3])
    p = rober_problem().p
    J_ad = jax.jacfwd(lambda uu: rober_rhs(uu, p, 0.0))(u)
    np.testing.assert_allclose(np.asarray(rober_jac(u, p, 0.0)),
                               np.asarray(J_ad), rtol=1e-15)


def test_orego_solves_on_fused_kernel():
    ens = EnsembleProblem(orego_problem(), 2)
    res = solve_ensemble_local(ens, alg="rodas5p", ensemble="kernel",
                               backend="pallas", dt0=1e-4, rtol=1e-7,
                               atol=1e-8)
    assert int(res.status) == 0
    assert np.all(np.asarray(res.u_final) > 0)        # concentrations stay +


def test_rodas_event_handling_uses_tableau_dense_output():
    # threshold crossing located on the stiffly-accurate interpolant
    from repro.core.events import Event
    prob = rober_problem(tspan=(0.0, 1e4))
    ev = Event(condition=lambda u, p, t: u[2] - 0.5, terminal=True,
               direction=1)
    res, einfo = solve_rosenbrock(prob.f, RODAS4, prob.u0, prob.p, 0.0, 1e4,
                                  1e-6, rtol=1e-8, atol=1e-10, jac=prob.jac,
                                  event=ev)
    t_star = float(einfo["event_t"])
    assert np.isfinite(t_star) and 0 < t_star < 1e4
    # the located state sits on the threshold
    assert abs(float(res.u_final[2]) - 0.5) < 1e-6


# ---------------------------------------------------------------------------
# initial_dt: the Hairer heuristic may be conservative but never 0/inf/NaN
# ---------------------------------------------------------------------------

def test_initial_dt_guard():
    prob = rober_problem()
    dt0 = initial_dt(prob.f, prob.u0, prob.p, 0.0, 1e5, 5, 1e-8, 1e-8)
    assert np.isfinite(float(dt0)) and 0 < float(dt0) <= 1e5
    # the produced step actually starts a converging Rodas solve
    res = solve_rosenbrock(prob.f, RODAS4, prob.u0, prob.p, 0.0, 1e3,
                           float(dt0), rtol=1e-6, atol=1e-8, jac=prob.jac)
    assert int(res.status) == 0

    # pathological norm ratios: huge |f|, tiny state — and the reverse
    def f_huge(u, p, t):
        return 1e300 * jnp.ones_like(u)

    def f_flat(u, p, t):
        return jnp.zeros_like(u)

    for f in (f_huge, f_flat):
        dt = initial_dt(f, jnp.asarray([1e-30, 1.0]), jnp.asarray([0.0]),
                        0.0, 10.0, 5, 1e-12, 1e-12)
        assert np.isfinite(float(dt)) and 0 < float(dt) <= 10.0, f


# ---------------------------------------------------------------------------
# pivoted batched LU: the contract the docstring promises
# ---------------------------------------------------------------------------

def _nondominant_batch():
    rng = np.random.default_rng(0)
    W_bad = np.array([[0.0, 2.0, 1.0],      # zero pivot: needs a row swap
                      [1.0, 0.0, 3.0],
                      [2.0, 1.0, 0.0]])
    W_ok = rng.normal(size=(3, 3)) + 5.0 * np.eye(3)
    W = jnp.asarray(np.stack([W_bad, W_ok]))
    b = jnp.asarray(rng.normal(size=(2, 3)))
    return W, b


def test_lu_pivoting_fixes_nondominant_systems():
    from repro.kernels.lu.kernel import lu_solve_lanes
    from repro.kernels.lu.ops import batched_solve
    from repro.kernels.lu.ref import ref_solve
    W, b = _nondominant_batch()
    ref = np.asarray(ref_solve(W, b))
    # the no-pivot kernel body fails this case (division by the zero pivot)
    x_nopiv = np.asarray(lu_solve_lanes(jnp.moveaxis(W, 0, -1), b.T,
                                        pivot=False))
    assert not np.all(np.isfinite(x_nopiv[:, 0]))
    # ... the pivoted kernel body solves it in-kernel, matching LAPACK
    x_piv = np.asarray(lu_solve_lanes(jnp.moveaxis(W, 0, -1), b.T)).T
    np.testing.assert_allclose(x_piv, ref, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(np.asarray(batched_solve(W, b)), ref,
                               rtol=1e-12, atol=1e-12)
    # even pivot=False is rescued at the ops layer now: the zero pivot is
    # flagged by the min-|pivot| output and routed to the jnp reference
    np.testing.assert_allclose(np.asarray(batched_solve(W, b, pivot=False)),
                               ref, rtol=1e-12, atol=1e-12)


def test_lu_singular_system_falls_back_to_jnp_reference():
    from repro.kernels.lu.kernel import lu_solve_pallas
    from repro.kernels.lu.ops import batched_solve
    from repro.kernels.lu.ref import ref_solve
    rng = np.random.default_rng(1)
    W_sing = np.array([[1.0, 2.0, 3.0],      # rank 2: elimination hits an
                       [2.0, 4.0, 6.0],      # exactly-zero pivot even after
                       [1.0, 1.0, 1.0]])     # row pivoting
    W_ok = rng.normal(size=(3, 3)) + 5.0 * np.eye(3)
    W = jnp.asarray(np.stack([W_sing, W_ok]))
    b = jnp.asarray(rng.normal(size=(2, 3)))
    # the raw kernel flags the singular lane (pivmin not > 0: zero or NaN
    # once a zero pivot poisons later rows) and emits a garbage column ...
    x_raw, pivmin = lu_solve_pallas(jnp.moveaxis(W, 0, -1), b.T, lane_tile=2)
    assert not bool(pivmin[0] > 0) and bool(pivmin[1] > 0)
    assert np.any(np.isinf(np.asarray(x_raw)[:, 0]))
    x = np.asarray(batched_solve(W, b))
    ref = np.asarray(ref_solve(W, b))
    # ... and batched_solve returns exactly the jnp reference's answer for
    # the singular lane (whatever garbage pattern the installed LAPACK
    # gives: all-NaN on some versions, a NaN/±inf mix on others)
    np.testing.assert_array_equal(x[0], ref[0])
    # and the healthy lane is untouched by the fallback
    np.testing.assert_allclose(x[1], ref[1], rtol=1e-12)
    # the zero matrix (pivmin NaN-poisoned at step 0) is also caught: the
    # ops layer returns the reference's answer, not the kernel's raw garbage
    W0 = jnp.asarray(np.stack([np.zeros((3, 3)), W_ok]))
    x0 = np.asarray(batched_solve(W0, b))
    ref0 = np.asarray(ref_solve(W0, b))
    np.testing.assert_array_equal(x0[0], ref0[0])
    # for this lane the raw kernel column differs from the reference, so
    # the equality above shows that batched_solve took the fallback (the
    # rank-2 lane's raw column may match the installed LAPACK's pattern)
    x_raw0, _ = lu_solve_pallas(jnp.moveaxis(W0, 0, -1), b.T, lane_tile=2)
    assert not np.array_equal(np.asarray(x_raw0)[:, 0], ref0[0],
                              equal_nan=True)


def test_lu_auto_lane_tile_shares_vmem_formula():
    from repro.kernels.ensemble_kernel import auto_lane_tile
    from repro.kernels.lu.ops import batched_solve, lu_lane_tile
    from repro.kernels.lu.ref import ref_solve
    # same §5.2 budget formula: tiles shrink as n^2 grows, 128-multiples
    assert lu_lane_tile(64) == auto_lane_tile(
        64, 0, 0, work_words=2 * 64 * 64 + 4 * 64)
    assert lu_lane_tile(3) % 128 == 0
    assert lu_lane_tile(96) < lu_lane_tile(8)
    # lane_tile=None (the auto path) solves a non-multiple-of-128 batch
    rng = np.random.default_rng(2)
    W = jnp.asarray(rng.normal(size=(37, 4, 4)) + 6.0 * np.eye(4))
    b = jnp.asarray(rng.normal(size=(37, 4)))
    np.testing.assert_allclose(np.asarray(batched_solve(W, b)),
                               np.asarray(ref_solve(W, b)),
                               rtol=1e-10, atol=1e-12)


def test_lu_kernel_docstring_matches_contract():
    # the bug this PR fixes: kernel.py promised an ops-layer singular
    # fallback that did not exist.  Keep code and docs agreeing.
    import inspect

    from repro.kernels.lu import kernel, ops
    assert "falls back to the jnp" in inspect.getdoc(kernel)
    assert "fall back" in inspect.getdoc(ops.batched_solve).replace(
        "falls back", "fall back")
    assert "pivot" in inspect.getdoc(ops.batched_solve)


# ---------------------------------------------------------------------------
# vmap lazy-W: the any()-gated refresh must survive batching
# ---------------------------------------------------------------------------

def _count_cond_eqns(jaxpr) -> int:
    """Recursively count `cond` primitives (vmap lowers an unreduced batched
    predicate to `select_n` — the cond disappears from the jaxpr entirely)."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            total += 1
        for val in eqn.params.values():
            subs = val if isinstance(val, (tuple, list)) else (val,)
            for sub in subs:
                inner = getattr(sub, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    total += _count_cond_eqns(inner)
    return total


def test_vmap_lazy_w_refresh_cond_survives_batching():
    """With `batch_axis` bound, the J/W refresh predicates are psum-reduced
    to batch scalars, so both refresh `lax.cond`s survive vmap as real
    branches; without it they are select-lowered (both branches always
    execute) and the njac savings are bookkeeping fiction."""
    prob = rober_problem()
    ep = rober_ensemble(4)
    u0s, ps = ep.materialize()

    def traced(batch_axis):
        def one(u0, p):
            return solve_rosenbrock(prob.f, RODAS4, u0, p, 0.0, 1.0, 1e-6,
                                    rtol=1e-4, atol=1e-6, jac=prob.jac,
                                    w_reuse=True, max_iters=2000,
                                    batch_axis=batch_axis).u_final
        vkw = {} if batch_axis is None else {"axis_name": batch_axis}
        return jax.make_jaxpr(jax.vmap(one, **vkw))(u0s, ps)

    assert _count_cond_eqns(traced("lanes").jaxpr) >= 2   # jac + refactor
    assert _count_cond_eqns(traced(None).jaxpr) == 0      # the old wart


def test_vmap_lazy_w_executes_fewer_jac_evals():
    """The njac counter reduction must correspond to fewer *executed*
    Jacobian applications under vmap, not just a smaller number."""
    import dataclasses

    counts = {"eager": 0, "lazy": 0}
    ens = rober_ensemble(4)
    _, ps = ens.materialize()

    def with_counting_jac(tag):
        def counting_jac(u, p, t):
            def bump(_):
                counts[tag] += 1
            jax.debug.callback(bump, t)
            return rober_jac(u, p, t)
        return EnsembleProblem(dataclasses.replace(ens.prob, jac=counting_jac),
                               4, ps=ps)

    kw = dict(alg="rodas4", ensemble="vmap", t0=0.0, tf=1.0, dt0=1e-6,
              rtol=1e-4, atol=1e-6)
    njac = {}
    for tag, wr in (("eager", False), ("lazy", True)):
        res = solve_ensemble_local(with_counting_jac(tag), w_reuse=wr, **kw)
        jax.block_until_ready(res.u_final)
        njac[tag] = int(np.max(np.asarray(res.njac)))
    jax.effects_barrier()
    assert counts["lazy"] < 0.7 * counts["eager"], counts
    assert njac["lazy"] < njac["eager"]
