"""Shared-engine tests: analytic optima, fleet CR1/CR2/CR3 vs the SLSQP
reference stack, penalty gradients, and fleet-scale CR3."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import CR1, CR2, CR3, SolveContext, solve, sweep
from repro.core.engine import EngineConfig, al_minimize, al_minimize_batched
from repro.core.fleet_solver import (FleetProblem, fleet_penalties,
                                     synthetic_fleet)


@pytest.fixture(scope="module")
def fp4(dr_problem):
    return FleetProblem.from_problem(dr_problem)


# ---------------------------------------------------------------------------
# Engine core on analytic problems
# ---------------------------------------------------------------------------
def test_engine_eq_constrained_qp():
    """min ||x − c||² s.t. Σx = 1 has closed form x = c + (1 − Σc)/n."""
    c = jnp.asarray([2.0, -1.0, 0.5, 0.5])

    def obj(x, _):
        return ((x - c) ** 2).sum()

    def eq(x, _):
        return jnp.atleast_1d(x.sum() - 1.0)

    x, aux = al_minimize(obj, lambda x: x, jnp.zeros(4), eq_residual=eq,
                         cfg=EngineConfig(inner_steps=300, outer_steps=6,
                                          lr=0.05, mu0=1.0))
    expect = np.asarray(c) + (1.0 - float(c.sum())) / 4.0
    np.testing.assert_allclose(np.asarray(x), expect, atol=1e-2)
    # The converged multiplier is the KKT multiplier 2(Σc − 1)/n · n = ...
    assert np.isfinite(float(aux["lam_eq"][0]))


def test_engine_ineq_constrained():
    """min ||x + 1||² s.t. x ≥ 0 → x* = 0 (constraint active)."""
    def obj(x, _):
        return ((x + 1.0) ** 2).sum()

    def g(x, _):
        return x

    x, _ = al_minimize(obj, lambda x: x, jnp.full((3,), 2.0),
                       ineq_residual=g,
                       cfg=EngineConfig(inner_steps=300, outer_steps=6,
                                        lr=0.05, mu0=1.0))
    np.testing.assert_allclose(np.asarray(x), np.zeros(3), atol=2e-2)


def test_engine_moment_dtype_f32_is_default_path():
    """moment_dtype='float32' must be byte-for-byte the legacy engine (the
    up/down casts are no-ops)."""
    def obj(x, _):
        return ((x - 0.3) ** 2).sum()

    def run(cfg):
        return al_minimize(obj, lambda x: x, jnp.zeros(5), cfg=cfg)[0]

    a = run(EngineConfig(inner_steps=80, outer_steps=2))
    b = run(EngineConfig(inner_steps=80, outer_steps=2,
                         moment_dtype="float32"))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_moment_dtype_bf16_tracks_f32():
    """bf16 Adam moments with the f32 master copy of x land near the f32
    optimum (moments only steer step sizes; precision loss is benign)."""
    c = jnp.asarray([2.0, -1.0, 0.5, 0.5])

    def obj(x, _):
        return ((x - c) ** 2).sum()

    def eq(x, _):
        return jnp.atleast_1d(x.sum() - 1.0)

    def run(mdt):
        cfg = EngineConfig(inner_steps=300, outer_steps=6, lr=0.05,
                           mu0=1.0, moment_dtype=mdt)
        return al_minimize(obj, lambda x: x, jnp.zeros(4), eq_residual=eq,
                           cfg=cfg)[0]

    x32, xbf = run("float32"), run("bfloat16")
    assert xbf.dtype == jnp.float32          # master copy stays f32
    np.testing.assert_allclose(np.asarray(xbf), np.asarray(x32), atol=5e-2)
    expect = np.asarray(c) + (1.0 - float(c.sum())) / 4.0
    np.testing.assert_allclose(np.asarray(xbf), expect, atol=5e-2)


def test_engine_moment_dtype_x64_reference_lane():
    """Parity lane for the mixed-precision knob: under x64, float64 vs
    float32 moments agree tightly on a fleet CR1 solve — the moment
    precision isn't load-bearing at these step counts. Subprocess so x64
    never leaks into this process's jit caches."""
    from conftest import run_in_subprocess
    run_in_subprocess("""
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro.core.api import CR1, SolveContext, solve
from repro.core.fleet_solver import synthetic_fleet

p = synthetic_fleet(6, hours=48, seed=0)
res = {m: solve(p, CR1(lam=1.45),
                ctx=SolveContext(steps=200, moment_dtype=m))
       for m in ("float64", "float32", "bfloat16")}
r64 = res["float64"].carbon_reduction_pct
assert abs(res["float32"].carbon_reduction_pct - r64) < 1e-3, res
assert abs(res["bfloat16"].carbon_reduction_pct - r64) < 0.05, res
print("ok")
""", devices=1)


def test_engine_batched_sweep_matches_unbatched():
    """vmapped hyper sweep = per-hyper solves (the compile-once Pareto path)."""
    def obj(x, h):
        return ((x - h) ** 2).sum()

    def project(x):
        return jnp.clip(x, 0.0, 1.0)

    cfg = EngineConfig(inner_steps=200, outer_steps=1, lr=0.05)
    hypers = jnp.asarray([0.2, 0.5, 2.0])
    xs = al_minimize_batched(obj, project, jnp.zeros(2), hypers, cfg=cfg)
    assert xs.shape == (3, 2)
    np.testing.assert_allclose(np.asarray(xs[:, 0]), [0.2, 0.5, 1.0],
                               atol=1e-2)
    for h, x in zip(hypers, xs):
        one, _ = al_minimize(obj, project, jnp.zeros(2), hyper=h, cfg=cfg)
        np.testing.assert_allclose(np.asarray(x), np.asarray(one), atol=1e-5)


def test_engine_batched_returns_stacked_aux_and_warm_starts():
    """A sweep must surface its solver state (stacked EngineState) so the
    next tick's sweep can warm-start lane-by-lane — and re-entering that
    state with a tiny budget must stay at each lane's optimum."""
    from repro.core.engine import EngineState

    def obj(x, h):
        return ((x - h) ** 2).sum()

    def project(x):
        return jnp.clip(x, 0.0, 1.0)

    hypers = jnp.asarray([0.2, 0.5, 2.0])
    cfg = EngineConfig(inner_steps=200, outer_steps=1, lr=0.05)
    xs, aux = al_minimize_batched(obj, project, jnp.zeros(2), hypers,
                                  cfg=cfg, return_aux=True)
    state = aux["state"]
    assert isinstance(state, EngineState)
    assert state.x.shape == (3, 2)        # leading sweep axis on every leaf
    assert state.mu.shape == (3,)
    np.testing.assert_allclose(np.asarray(xs), np.asarray(state.x))

    # a short warm budget stays at each lane's optimum (fresh Adam moments
    # wiggle the first steps, so compare to the optima, not bitwise to xs)
    warm_xs = al_minimize_batched(
        obj, project, jnp.zeros(2), hypers, init=state,
        cfg=EngineConfig(inner_steps=50, outer_steps=1, lr=0.05))
    np.testing.assert_allclose(np.asarray(warm_xs[:, 0]), [0.2, 0.5, 1.0],
                               atol=2e-2)

    # positional return unchanged for existing callers
    xs_only = al_minimize_batched(obj, project, jnp.zeros(2), hypers,
                                  cfg=cfg)
    np.testing.assert_allclose(np.asarray(xs_only), np.asarray(xs),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# fleet_penalties is the single penalty path — its gradients must be exact
# ---------------------------------------------------------------------------
def test_fleet_penalties_grad_matches_finite_differences(fp4, rng):
    with jax.enable_x64(True):
        D0 = jnp.asarray(rng.uniform(-0.5, 0.5, size=(fp4.W, fp4.T)))

        def f(D):
            return fleet_penalties(fp4, D).sum()

        g = jax.grad(f)(D0)
        eps = 1e-5
        for _ in range(12):
            i, t = int(rng.integers(fp4.W)), int(rng.integers(fp4.T))
            e = np.zeros((fp4.W, fp4.T))
            e[i, t] = eps
            fd = (f(D0 + jnp.asarray(e)) - f(D0 - jnp.asarray(e))) / (2 * eps)
            assert abs(float(fd) - float(g[i, t])) < 1e-6


def test_fleet_penalties_kernel_path_grad_matches_jnp(fp4, rng):
    """The Pallas feature kernel's custom VJP must agree with the jnp path
    (the engine differentiates through it on TPU)."""
    D0 = jnp.asarray(rng.uniform(-0.5, 0.5, size=(fp4.W, fp4.T)))
    g_jnp = jax.grad(lambda D: fleet_penalties(fp4, D, use_kernel=False)
                     .sum())(D0)
    g_ker = jax.grad(lambda D: fleet_penalties(fp4, D, use_kernel=True)
                     .sum())(D0)
    np.testing.assert_allclose(np.asarray(g_ker), np.asarray(g_jnp),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# DRProblem <-> FleetProblem round trip
# ---------------------------------------------------------------------------
def test_problem_round_trip(dr_problem, fp4, rng):
    p2 = fp4.to_problem()
    assert p2.names == dr_problem.names
    assert (p2.batch_mask == dr_problem.batch_mask).all()
    D = jnp.asarray(rng.uniform(-1, 1, size=(fp4.W, fp4.T)))
    np.testing.assert_allclose(
        np.asarray(p2.penalties(D, smooth=0.0)),
        np.asarray(fleet_penalties(fp4, D)), rtol=1e-5, atol=1e-5)
    fp2 = FleetProblem.from_problem(p2)
    np.testing.assert_allclose(fp2.usage, fp4.usage)
    np.testing.assert_allclose(fp2.betas, fp4.betas)
    np.testing.assert_allclose(fp2.k, fp4.k, rtol=1e-12)


# ---------------------------------------------------------------------------
# Policy adapters vs the SLSQP validation reference (4-workload paper fleet)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_cr1_fleet_matches_slsqp_per_workload(dr_problem, fp4):
    from repro.core.policies import cr1_spec
    from repro.core.solver import solve_slsqp
    ref = solve_slsqp(cr1_spec(dr_problem, 1.4), maxiter=250)
    got = solve(fp4, CR1(lam=1.4))
    pens = np.asarray(fleet_penalties(fp4, jnp.asarray(got.D)))
    assert abs(got.carbon_reduction_pct - ref.carbon_reduction_pct) < 1.5
    assert abs(got.total_penalty_pct - ref.total_penalty_pct) < 1.5
    # Per-workload penalties agree within 3% of each entitlement.
    np.testing.assert_array_less(
        np.abs(pens - ref.per_penalty) / np.asarray(fp4.entitlement), 0.03)


@pytest.mark.slow
def test_cr2_fleet_matches_slsqp_per_workload(dr_problem, fp4):
    """RTS rows match the SLSQP stack's penalties; batch rows land at or
    below them (the preservation projection bounds attainable deferral
    penalties — fairer than required, never unfairer)."""
    from repro.core.policies import cr2_spec
    from repro.core.solver import solve_slsqp
    ref = solve_slsqp(cr2_spec(dr_problem, 0.78), maxiter=250)
    got = solve(fp4, CR2(cap_frac=0.78))
    pens = np.asarray(fleet_penalties(fp4, jnp.asarray(got.D)))
    assert abs(got.carbon_reduction_pct - ref.carbon_reduction_pct) < 1.5
    assert abs(got.total_penalty_pct - ref.total_penalty_pct) < 1.5
    E = np.asarray(fp4.entitlement)
    rts = ~np.asarray(fp4.is_batch)
    np.testing.assert_array_less(
        np.abs(pens - ref.per_penalty)[rts] / E[rts], 0.01)
    assert (pens[~rts] <= ref.per_penalty[~rts] + 0.05).all()


@pytest.mark.slow
def test_cr3_fleet_matches_slsqp_reference(dr_problem, fp4):
    """Acceptance: decentralized fleet CR3 within 2% of the paper-stack
    CR3 on carbon reduction and total penalty, and fiscally balanced."""
    from repro.core.policies import cr3_fiscal_balance
    from repro.core.solver import solve_cr3
    ref, rho_ref = solve_cr3(dr_problem, rho=0.02)
    got = solve(fp4, CR3(rho=0.02))
    rho_got = got.extras["rho"]
    assert abs(got.carbon_reduction_pct - ref.carbon_reduction_pct) < 2.0
    assert abs(got.total_penalty_pct - ref.total_penalty_pct) < 2.0
    paid, collected = cr3_fiscal_balance(dr_problem, got.D, rho_got)
    assert paid <= collected + 1e-6              # Eq. 6
    assert got.preservation_violation < 1e-3


def test_cr1_sweep_matches_single_solves(fp4):
    lams = [1.2, 1.6]
    ctx = SolveContext(steps=300)
    got = sweep(fp4, [CR1(lam=lam) for lam in lams], ctx=ctx)
    for lam, r in zip(lams, got):
        one = solve(fp4, CR1(lam=lam), ctx=ctx)
        assert abs(r.carbon_reduction_pct - one.carbon_reduction_pct) < 1e-4
        assert abs(r.total_penalty_pct - one.total_penalty_pct) < 1e-4


@pytest.mark.slow
def test_cr3_fleet_scales_to_512_workloads():
    p = synthetic_fleet(512)
    r = solve(p, CR3(outer=2, clearing_iters=2),
              ctx=SolveContext(steps=150))
    assert r.D.shape == (512, 48)
    assert np.isfinite(r.carbon_reduction_pct)
    assert r.preservation_violation < 1e-3
    assert r.extras["rho"] > 0
    # box respected
    hi = np.minimum(0.5 * p.entitlement[:, None], p.usage)
    assert (r.D <= hi + 1e-4).all()
    assert (r.D[~p.is_batch] >= -1e-5).all()     # RTS curtail-only
