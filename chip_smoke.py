"""Smoke run of the fused ensemble solvers on a TPU, through the front door.

    python chip_smoke.py                        # one chip, full sizes
    python chip_smoke.py --chips 4              # the mesh path on four chips
    JAX_PLATFORMS=cpu python chip_smoke.py --n 512    # CPU rehearsal

One process, float32, x64 off.  Every phase goes through
`repro.core.ensemble.solve_ensemble_local` (`repro.core.api.solve_ensemble`
for the mesh path), jit-compiled once, timed once after a warm-up run, and
checked against a reference with a tolerance written next to its reason.

  lorenz_fixed     Lorenz sweep, 2^22 trajectories, Tsit5, dt = 1e-3 on
                   [0, 1], 4 saves; pallas vs xla over the whole ensemble,
                   and 256 strided trajectories vs a NumPy f64 RK4.
  lorenz_adaptive  the same sweep at 2^20, pallas, rtol = atol = 1e-5,
                   5 saves, vs the f64 RK4.
  gbm              GBM Monte Carlo (r = 1.5, v = 0.2), Euler-Maruyama,
                   dt = 1/200, 2^22 paths, in-kernel Threefry noise; mean
                   vs closed form, pallas vs xla.
  stiff            Van der Pol sweep, 2^16 trajectories, Rosenbrock23
                   (inlined lanes LU); pallas vs xla, and 256 strided
                   trajectories vs SciPy Radau in f64.

With ``--chips 4`` only the mesh path runs: the Lorenz fixed-step and GBM
ensembles at 4x the one-chip size, sharded over all devices, against the
same ensembles solved on one device.

Each phase prints one line.  On a TPU whose phases all pass, the last line
is ``{"ok": true, "device": {...}}``.  A failed phase, a phase whose Pallas
kernel did not compile to a TPU kernel, or a device that is not a TPU
exits non-zero without that line.  Without ``--n`` a non-TPU device fails
at once; with it the phases run in Pallas interpret mode first.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.de_problems import (gbm_problem, lorenz_ensemble,  # noqa: E402
                                       vdp_ensemble)
from repro.core import EnsembleProblem, solve_ensemble_local  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

F32 = jnp.float32
REF_LANES = 256


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def run_timed(fn, *args):
    """jit, compile, warm up, then time one run.  Returns (out, compile_s,
    wall_s, kernel), kernel = "mosaic" when the compiled program holds a
    TPU kernel (`tpu_custom_call`)."""
    tic = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - tic
    kernel = "mosaic" if "tpu_custom_call" in compiled.as_text() else "none"
    jax.block_until_ready(compiled(*args))
    tic = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, compile_s, time.perf_counter() - tic, kernel


@jax.jit
def scaled_err(a, b):
    """max |a - b| / (1 + |b|): absolute near 0, relative for large states."""
    return jnp.max(jnp.abs(a - b) / (1.0 + jnp.abs(b)))


def scaled_err_np(a, ref):
    a = np.asarray(a, np.float64)
    return float(np.max(np.abs(a - ref) / (1.0 + np.abs(ref))))


def strided(N: int, k: int = REF_LANES) -> np.ndarray:
    return np.unique(np.linspace(0, N - 1, min(k, N)).round().astype(int))


def lorenz_rk4(u0s, ps, t_saves, dt=1e-4):
    """NumPy f64 classical RK4 on the given trajectories; (K, S, 3)."""
    u = np.asarray(u0s, np.float64).T.copy()
    s, r, b = np.asarray(ps, np.float64).T

    def f(v):
        x, y, z = v
        return np.stack([s * (y - x), r * x - y - x * z, x * y - b * z])

    save_steps = [int(round(t / dt)) for t in t_saves]
    out = []
    for k in range(1, save_steps[-1] + 1):
        k1 = f(u)
        k2 = f(u + 0.5 * dt * k1)
        k3 = f(u + 0.5 * dt * k2)
        k4 = f(u + dt * k3)
        u = u + (dt / 6.0) * (k1 + 2.0 * k2 + 2.0 * k3 + k4)
        if k in save_steps:
            out.append(u.T.copy())
    return np.stack(out, axis=1)


def vdp_radau(u0s, ps, tf):
    """SciPy Radau (f64, rtol 1e-10) final states; (K, 2)."""
    from scipy.integrate import solve_ivp
    out = []
    for u0, p in zip(np.asarray(u0s, np.float64), np.asarray(ps, np.float64)):
        mu = p[0]

        def f(t, u, mu=mu):
            return [u[1], mu * ((1.0 - u[0] ** 2) * u[1]) - u[0]]

        sol = solve_ivp(f, (0.0, tf), u0, method="Radau", rtol=1e-10,
                        atol=1e-10)
        out.append(sol.y[:, -1])
    return np.stack(out)


def report(name, checks, **fields):
    """Print one phase line; a phase passes when every (value, tol) check
    holds and its Pallas runs compiled to a TPU kernel on a TPU."""
    ok = all(bool(v <= tol) for v, tol in checks.values())
    parts = [f"{k}={v}" for k, v in fields.items()]
    parts += [f"{k}={v!r}(tol {tol!r})" for k, (v, tol) in checks.items()]
    print(f"[{name}] " + " ".join(parts) + f" ok={ok}", flush=True)
    return ok


def interpreted(kernel: str, on_tpu: bool) -> float:
    """1.0 where a TPU run went without a Mosaic kernel (ran interpreted),
    else 0.0; off the chip (rehearsal) interpret mode is expected."""
    return 0.0 if (kernel == "mosaic" or not on_tpu) else 1.0


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------

def phase_lorenz_fixed(N, on_tpu):
    ep = lorenz_ensemble(N, dtype=F32)
    u0s, ps = ep.materialize()
    kw = dict(alg="tsit5", ensemble="kernel", t0=0.0, tf=1.0, dt0=1e-3,
              adaptive=False, n_steps=1000, save_every=250)

    def solver(backend):
        def fn(u, p):
            r = solve_ensemble_local(EnsembleProblem(ep.prob, N, u0s=u, ps=p),
                                     backend=backend, **kw)
            return r.us, r.u_final
        return fn

    (us_p, uf_p), c_p, w_p, k_p = run_timed(solver("pallas"), u0s, ps)
    (us_x, uf_x), c_x, w_x, _ = run_timed(solver("xla"), u0s, ps)
    idx = strided(N)
    ref = lorenz_rk4(np.asarray(u0s[idx]), np.asarray(ps[idx]),
                     [0.25, 0.5, 0.75, 1.0])
    return report(
        "lorenz_fixed", {
            # same Tsit5 steps in f32, but the kernel accumulates t += dt
            # (1000 f32 adds drift the grid by up to ~3e-5) and hits the
            # saves by dense output, while xla steps on t0 + k·dt: drift ×
            # |du/dt| (≲ 100) over 1 + |u| gives ~1e-4 (1.7e-4 in the CPU
            # rehearsal); rounding (~eps·sqrt(7000) ≈ 5e-6, grown by the
            # Lorenz transient) adds less
            "pallas_vs_xla": (float(scaled_err(us_p, us_x)), 1e-3),
            # same budget against the f64 RK4 (its own truncation error at
            # dt = 1e-4 is ~1e-13, Tsit5's at 1e-3 is ~1e-12)
            "pallas_vs_f64_rk4": (scaled_err_np(np.asarray(us_p[idx]), ref),
                                  1e-3),
            "xla_vs_f64_rk4": (scaled_err_np(np.asarray(us_x[idx]), ref),
                               1e-3),
            "ran_interpreted": (interpreted(k_p, on_tpu), 0.0),
            "finite": (float(not bool(jnp.all(jnp.isfinite(uf_p)))), 0.0),
        },
        N=N, us=tuple(us_p.shape), kernel=k_p,
        pallas_compile_s=round(c_p, 3), pallas_wall_s=round(w_p, 4),
        xla_compile_s=round(c_x, 3), xla_wall_s=round(w_x, 4))


def phase_lorenz_adaptive(N, on_tpu):
    ep = lorenz_ensemble(N, dtype=F32)
    u0s, ps = ep.materialize()
    saveat = [0.2, 0.4, 0.6, 0.8, 1.0]

    def fn(u, p):
        r = solve_ensemble_local(
            EnsembleProblem(ep.prob, N, u0s=u, ps=p), alg="tsit5",
            ensemble="kernel", backend="pallas", t0=0.0, tf=1.0, dt0=1e-3,
            saveat=jnp.asarray(saveat, F32), rtol=1e-5, atol=1e-5)
        return r.us, r.naccept, r.nreject, r.status

    (us, nacc, nrej, status), c, w, k = run_timed(fn, u0s, ps)
    idx = strided(N)
    ref = lorenz_rk4(np.asarray(u0s[idx]), np.asarray(ps[idx]), saveat)
    return report(
        "lorenz_adaptive", {
            # per-step error control at 1e-5 (f32 keeps ~7 digits, so the
            # controller's target sits 2 decades above rounding); the global
            # error is the local target summed over the steps and grown by
            # the same transient: allow 100x the tolerance
            "pallas_vs_f64_rk4": (scaled_err_np(np.asarray(us[idx]), ref),
                                  1e-3),
            "status": (float(status), 0.0),
            "ran_interpreted": (interpreted(k, on_tpu), 0.0),
        },
        N=N, us=tuple(us.shape), kernel=k, compile_s=round(c, 3),
        wall_s=round(w, 4), naccept_total=int(jnp.sum(nacc)),
        nreject_total=int(jnp.sum(nrej)), naccept_max=int(jnp.max(nacc)),
        nreject_max=int(jnp.max(nrej)))


def phase_gbm(N, on_tpu):
    r_, v_, x0, n_steps = 1.5, 0.2, 0.1, 200
    dt = 1.0 / n_steps
    ep = EnsembleProblem(gbm_problem(r=r_, v=v_), N)
    u0s, ps = ep.materialize()

    def solver(backend):
        def fn(u, p):
            r = solve_ensemble_local(
                EnsembleProblem(ep.prob, N, u0s=u, ps=p), alg="em",
                ensemble="kernel", backend=backend, t0=0.0, tf=1.0, dt0=dt,
                n_steps=n_steps, save_every=n_steps, seed=0)
            return r.u_final
        return fn

    uf_p, c_p, w_p, k_p = run_timed(solver("pallas"), u0s, ps)
    uf_x, c_x, w_x, _ = run_timed(solver("xla"), u0s, ps)
    bitwise = bool(jnp.array_equal(uf_p, uf_x))
    print(f"[gbm] pallas_xla_bitwise={bitwise}", flush=True)
    x = np.asarray(uf_p, np.float64).ravel()   # 3 independent rows per path
    mean, se = float(x.mean()), float(x.std() / np.sqrt(x.size))
    # Euler-Maruyama's mean is exactly x0·(1 + r·dt)^n; it sits below the
    # SDE's x0·e^{rT} by EM's O(dt) weak error (0.56% here, far above the
    # standard error at this N), so the 4-SE check is against the former
    # and the latter must lie within that known bias plus 4 SE
    em_mean = x0 * (1.0 + r_ * dt) ** n_steps
    sde_mean = x0 * float(np.exp(r_))
    return report(
        "gbm", {
            "mean_vs_em_closed_form_in_se": (abs(mean - em_mean) / se, 4.0),
            "mean_vs_exp_rT_minus_em_bias_in_se": (
                (abs(mean - sde_mean) - abs(em_mean - sde_mean)) / se, 4.0),
            # identical Threefry integer streams; log/sqrt/cos may differ by
            # a few ulp between kernel and XLA lowerings, compounded over
            # 200 multiplicative steps of size v·sqrt(dt) ≈ 0.014
            "pallas_vs_xla": (float(scaled_err(uf_p, uf_x)), 1e-4),
            "ran_interpreted": (interpreted(k_p, on_tpu), 0.0),
        },
        N=N, u_final=tuple(uf_p.shape), kernel=k_p, mean=mean, se=se,
        em_mean=em_mean, exp_rT=sde_mean,
        pallas_compile_s=round(c_p, 3), pallas_wall_s=round(w_p, 4),
        xla_compile_s=round(c_x, 3), xla_wall_s=round(w_x, 4))


def phase_stiff(N, on_tpu):
    ep = vdp_ensemble(N, dtype=F32)
    u0s, ps = ep.materialize()
    tol = 1e-5

    def solver(backend, lane_tile):
        def fn(u, p):
            r = solve_ensemble_local(
                EnsembleProblem(ep.prob, N, u0s=u, ps=p), alg="rosenbrock23",
                ensemble="kernel", backend=backend, rtol=tol, atol=tol,
                lane_tile=lane_tile)
            return r.u_final, r.status, r.naccept
        return fn

    (uf_p, st_p, nacc), c_p, w_p, k_p = run_timed(solver("pallas", None),
                                                  u0s, ps)
    (uf_x, st_x, _), c_x, w_x, _ = run_timed(solver("xla", 4096), u0s, ps)
    idx = strided(N)
    ref = vdp_radau(np.asarray(u0s[idx]), np.asarray(ps[idx]),
                    float(ep.prob.tspan[1]))
    return report(
        "stiff", {
            # both solve to rtol = atol = 1e-5 in f32; rounding can flip an
            # accept/reject and so move each by up to its global error, a
            # small multiple of the tolerance for this order-2 pair
            "pallas_vs_xla": (float(scaled_err(uf_p, uf_x)), 100 * tol),
            "pallas_vs_f64_radau": (scaled_err_np(np.asarray(uf_p[idx]), ref),
                                    100 * tol),
            "status": (float(max(int(st_p), int(st_x))), 0.0),
            "ran_interpreted": (interpreted(k_p, on_tpu), 0.0),
        },
        N=N, u_final=tuple(uf_p.shape), kernel=k_p,
        naccept_mean=float(jnp.mean(nacc)),
        pallas_compile_s=round(c_p, 3), pallas_wall_s=round(w_p, 4),
        xla_compile_s=round(c_x, 3), xla_wall_s=round(w_x, 4))


# ---------------------------------------------------------------------------
# four-chip mesh path
# ---------------------------------------------------------------------------

def phase_mesh(N_lorenz, N_gbm, on_tpu):
    from repro.core.api import solve_ensemble
    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh()
    n_dev = len(jax.devices())
    one = jax.devices()[0]
    ok = True
    cases = [
        ("mesh_lorenz_fixed", lorenz_ensemble(N_lorenz, dtype=F32),
         dict(alg="tsit5", ensemble="kernel", backend="pallas", t0=0.0,
              tf=1.0, dt0=1e-3, adaptive=False, n_steps=1000,
              save_every=250),
         # same kernel, same lanes: only the tiling of the grid differs
         1e-5),
        ("mesh_gbm", EnsembleProblem(gbm_problem(r=1.5, v=0.2), N_gbm),
         dict(alg="em", ensemble="kernel", backend="pallas", t0=0.0, tf=1.0,
              dt0=1.0 / 200, n_steps=200, save_every=200, seed=0),
         # each shard draws the global (seed; step, row, lane) stream via
         # its lane_offset, so the paths are the one-device paths
         1e-5),
    ]
    for name, ep, kw, tol in cases:
        u0s, ps = ep.materialize()
        N = u0s.shape[0]

        def sharded(u, p, ep=ep, kw=kw, N=N):
            return solve_ensemble(EnsembleProblem(ep.prob, N, u0s=u, ps=p),
                                  mesh=mesh, **kw).u_final

        def local(u, p, ep=ep, kw=kw, N=N):
            return solve_ensemble_local(
                EnsembleProblem(ep.prob, N, u0s=u, ps=p), **kw).u_final

        uf_s, c_s, w_s, k_s = run_timed(sharded, u0s, ps)
        u1, p1 = jax.device_put(u0s, one), jax.device_put(ps, one)
        uf_1, c_1, w_1, _ = run_timed(local, u1, p1)
        spans = len(uf_s.sharding.device_set)
        bitwise = bool(jnp.array_equal(jax.device_put(uf_s, one), uf_1))
        ok &= report(
            name, {
                "sharded_vs_one_device": (
                    float(scaled_err(jax.device_put(uf_s, one), uf_1)), tol),
                "devices_missing": (float(n_dev - spans), 0.0),
                "ran_interpreted": (interpreted(k_s, on_tpu), 0.0),
            },
            N=N, devices=n_dev, output_spans=spans, bitwise=bitwise,
            kernel=k_s, sharded_compile_s=round(c_s, 3),
            sharded_wall_s=round(w_s, 4), one_device_compile_s=round(c_1, 3),
            one_device_wall_s=round(w_1, 4))
    return ok


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the mesh path over four devices")
    ap.add_argument("--n", type=int, default=None,
                    help="shrink every ensemble to N trajectories "
                         "(CPU rehearsal; such a run never reports ok)")
    args = ap.parse_args()

    use_compile_cache()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and args.n is None:
        print(f"chip_smoke: device platform is {dev.platform!r}, not 'tpu'; "
              "no CPU fallback (pass --n to rehearse)", file=sys.stderr)
        return 2
    if args.chips == 4 and len(jax.devices()) != 4:
        print(f"chip_smoke: --chips 4 needs 4 devices, found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2
    print(f"# device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__} "
          f"x64={jax.config.jax_enable_x64}", flush=True)

    def size(full):
        return full if args.n is None else args.n

    if args.chips == 4:
        ok = phase_mesh(size(4 * 2 ** 22), size(4 * 2 ** 22), on_tpu)
    else:
        ok = True
        ok &= phase_lorenz_fixed(size(2 ** 22), on_tpu)
        ok &= phase_lorenz_adaptive(size(2 ** 20), on_tpu)
        ok &= phase_gbm(size(2 ** 22), on_tpu)
        ok &= phase_stiff(size(2 ** 16), on_tpu)

    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    if not on_tpu:
        print(f"chip_smoke: rehearsal on {dev.platform!r} passed its phases; "
              "only a TPU run reports ok", file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
