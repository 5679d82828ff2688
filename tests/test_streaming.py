"""Rolling-horizon streaming DR: forecast streams, engine warm starts,
warm-vs-cold re-solve quality, and the online control loop."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api
from repro.core.api import CR1, CR2, SolveContext, solve
from repro.core.carbon import ForecastStream, caiso_2021
from repro.core.engine import EngineConfig, EngineState, al_minimize
from repro.core.fleet_solver import (_jit_view, cr2_cap, cr2_reference_fleet,
                                     synthetic_fleet,
                                     synthetic_regional_fleet)
from repro.core.streaming import RollingHorizonSolver
from repro.obs import spans


# ---------------------------------------------------------------------------
# ForecastStream
# ---------------------------------------------------------------------------
def test_forecast_stream_shapes_and_determinism():
    s = ForecastStream.caiso(n_ticks=6, horizon=48, seed=3)
    assert s.n_ticks >= 6
    f0 = s.forecast(2)
    assert f0.shape == (48,)
    assert (f0 >= 0).all()
    np.testing.assert_array_equal(f0, s.forecast(2))   # re-issue == same
    assert not np.array_equal(f0, s.forecast(3))       # revisions differ


def test_forecast_error_grows_with_lead_time():
    s = ForecastStream.caiso(n_ticks=40, horizon=48, seed=0,
                             revision_sigma=0.05)
    near, far = [], []
    for t in range(40):
        f = s.forecast(t)
        actual = s.actual[t:t + 48]
        rel = np.abs(f / np.maximum(actual, 1e-9) - 1.0)
        near.append(rel[0])
        far.append(rel[-1])
    # committed-hour (nowcast) error is small; day-ahead tail error larger
    assert np.mean(near) < 0.05
    assert np.mean(far) > 2.0 * np.mean(near)


def test_forecast_stream_replay_mode():
    snaps = np.arange(3 * 8, dtype=float).reshape(3, 8)
    s = ForecastStream(actual=np.ones(16), horizon=8, replay=snaps)
    assert s.n_ticks == 3
    np.testing.assert_array_equal(s.forecast(1), snaps[1])
    with pytest.raises(IndexError):
        s.forecast(3)
    with pytest.raises(ValueError):
        ForecastStream(actual=np.ones(16), horizon=8,
                       replay=np.ones((3, 7)))


def test_forecast_stream_replay_clamped_to_realized_hours():
    """Regression: more replay snapshots than realized hours must clamp
    `n_ticks` — previously `forecast()` succeeded on ticks whose
    `realized()` hour did not exist, crashing mid-run with IndexError."""
    snaps = np.ones((5, 8))
    s = ForecastStream(actual=np.ones(3), horizon=8, replay=snaps)
    assert s.n_ticks == 3                       # min(replay rows, actual)
    assert s.forecast(2).shape == (8,)
    assert s.realized(2) == 1.0
    with pytest.raises(IndexError):
        s.forecast(3)                           # beyond the realized range
    with pytest.raises(IndexError):
        s.realized(3)
    # a full run over n_ticks never touches a missing realized hour
    for t in range(s.n_ticks):
        s.forecast(t)
        s.realized(t)


def test_forecast_stream_realized_is_actual():
    sig = caiso_2021(60)
    s = ForecastStream(actual=sig.mci, horizon=48)
    assert s.realized(5) == float(sig.mci[5])


def test_forecast_stream_replay_tick_boundary():
    """The last valid tick is n_ticks - 1 exactly; n_ticks itself must
    raise for both forecast() and realized() in replay mode."""
    snaps = np.arange(4 * 6, dtype=float).reshape(4, 6)
    s = ForecastStream(actual=np.ones(4), horizon=6, replay=snaps)
    assert s.n_ticks == 4
    np.testing.assert_array_equal(s.forecast(s.n_ticks - 1), snaps[3])
    assert s.realized(s.n_ticks - 1) == 1.0
    with pytest.raises(IndexError):
        s.forecast(s.n_ticks)
    with pytest.raises(IndexError):
        s.forecast(-1)


def test_forecast_stream_horizon_longer_than_actual():
    """Revision mode with horizon > len(actual) supports zero ticks (no
    full horizon exists) and says so via IndexError, not a crash deep in
    the revision model."""
    s = ForecastStream(actual=np.ones(10), horizon=48)
    assert s.n_ticks == 0
    with pytest.raises(IndexError, match=r"\[0, 0\)"):
        s.forecast(0)
    # replay mode: snapshots may cover a longer horizon than the realized
    # series; ticks clamp to the realized hours
    s2 = ForecastStream(actual=np.ones(2), horizon=48,
                        replay=np.ones((5, 48)))
    assert s2.n_ticks == 2
    assert s2.forecast(1).shape == (48,)
    with pytest.raises(IndexError):
        s2.forecast(2)


# ---------------------------------------------------------------------------
# Engine warm starts
# ---------------------------------------------------------------------------
def test_engine_state_shifted_rolls_time_axis():
    st = EngineState(x=jnp.asarray([[1.0, 2.0, 3.0], [4.0, 5.0, 6.0]]),
                     lam_eq=jnp.asarray([7.0]), lam_in=jnp.zeros(0),
                     mu=jnp.asarray(0.5))
    sh = st.shifted(1)
    np.testing.assert_allclose(np.asarray(sh.x),
                               [[2.0, 3.0, 0.0], [5.0, 6.0, 0.0]])
    np.testing.assert_allclose(np.asarray(sh.lam_eq), [7.0])   # carried
    assert float(sh.mu) == 0.5


def test_engine_warm_start_preserves_optimum():
    """A converged state re-entered with a tiny budget stays converged."""
    c = jnp.asarray([2.0, -1.0, 0.5, 0.5])

    def obj(x, _):
        return ((x - c) ** 2).sum()

    def eq(x, _):
        return jnp.atleast_1d(x.sum() - 1.0)

    _, aux = al_minimize(obj, lambda x: x, jnp.zeros(4), eq_residual=eq,
                         cfg=EngineConfig(inner_steps=300, outer_steps=6,
                                          lr=0.05, mu0=1.0))
    x2, aux2 = al_minimize(obj, lambda x: x, jnp.zeros(4), eq_residual=eq,
                           init=aux["state"],
                           cfg=EngineConfig(inner_steps=25, outer_steps=1,
                                            lr=0.05, mu0=1.0))
    expect = np.asarray(c) + (1.0 - float(c.sum())) / 4.0
    np.testing.assert_allclose(np.asarray(x2), expect, atol=1e-2)
    assert isinstance(aux2["state"], EngineState)


def test_engine_cold_state_equals_default_path():
    """init=EngineState.cold(...) is byte-for-byte the legacy cold solve."""
    def obj(x, _):
        return ((x - 0.3) ** 2).sum()

    cfg = EngineConfig(inner_steps=50, outer_steps=2, mu0=2.0)

    def g(x, _):
        return x

    x_a, _ = al_minimize(obj, lambda x: x, jnp.zeros(3), ineq_residual=g,
                         cfg=cfg)
    x_b, _ = al_minimize(obj, lambda x: x, jnp.zeros(3), ineq_residual=g,
                         init=EngineState.cold(jnp.zeros(3), n_in=3,
                                               mu0=2.0), cfg=cfg)
    np.testing.assert_array_equal(np.asarray(x_a), np.asarray(x_b))


# ---------------------------------------------------------------------------
# Warm-started fleet re-solves on a shifted horizon
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_warm_resolve_matches_cold_on_shifted_horizon():
    """Shift the window one hour, warm-start at 1/4 the budget: the re-solve
    must reach the cold solve's CR1 objective (pp units) to 0.1 pp."""
    lam = 1.45
    p = synthetic_fleet(8)
    prev = solve(p, CR1(lam=lam), ctx=SolveContext(steps=600))
    shifted = dataclasses.replace(
        p, mci=np.roll(p.mci, -1), usage=np.roll(p.usage, -1, axis=1),
        jobs=np.roll(p.jobs, -1, axis=1))
    warm = solve(shifted, CR1(lam=lam),
                 ctx=SolveContext(steps=150, warm=prev.state.shifted(1)))
    cold = solve(shifted, CR1(lam=lam), ctx=SolveContext(steps=600))

    def obj(r):
        return lam * r.total_penalty_pct - r.carbon_reduction_pct

    assert obj(warm) <= obj(cold) + 0.1
    assert warm.preservation_violation < 1e-3


# ---------------------------------------------------------------------------
# RollingHorizonSolver control loop
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_rolling_horizon_cr1_commits_and_accounts():
    p = synthetic_fleet(6)
    stream = ForecastStream.caiso(n_ticks=4, horizon=p.T, seed=1)
    rhs = RollingHorizonSolver(p, stream, policy="cr1",
                               cold_steps=300, warm_steps=80)
    report = rhs.run(4)
    assert report.committed.shape == (6, 4)
    # tick 0 cold, then warm budgets
    assert [t.inner_steps for t in report.ticks] == [300, 80, 80, 80]
    assert report.total_inner_steps == 300 + 3 * 80
    # committed hours respect the fleet box of their window
    for tk in report.ticks:
        u_t = np.roll(p.usage, -tk.tick, axis=1)[:, 0]
        hi = np.minimum(0.5 * p.entitlement, u_t)
        assert (tk.committed <= hi + 1e-4).all()
        assert (tk.committed[~p.is_batch] >= -1e-5).all()
    # ledger identities
    assert report.realized_carbon == pytest.approx(
        sum(t.committed.sum() * t.realized_mci for t in report.ticks))
    assert 0 < report.realized_reduction_pct < 100
    assert np.isfinite(report.forecast_error_pct)


def test_rolling_horizon_validates_inputs():
    p = synthetic_fleet(2)
    stream = ForecastStream.caiso(n_ticks=2, horizon=24)
    with pytest.raises(ValueError):
        RollingHorizonSolver(p, stream)          # horizon mismatch
    stream48 = ForecastStream.caiso(n_ticks=2, horizon=p.T)
    # unknown policy names fail at construction, naming the registry's
    # choices — not as an opaque failure at the first step()
    with pytest.raises(ValueError,
                       match="registered policies.*cr1.*cr2.*cr3"):
        RollingHorizonSolver(p, stream48, policy="cr9")
    with pytest.raises(TypeError, match="DRPolicy"):
        RollingHorizonSolver(p, stream48, policy=1.45)
    rhs = RollingHorizonSolver(p, stream48, cold_steps=50, warm_steps=20)
    with pytest.raises(RuntimeError):
        rhs.report()                             # nothing committed yet


def test_rolling_horizon_accepts_policy_objects():
    """A DRPolicy object IS the configuration: string names resolve to the
    equivalent object via the registry + legacy knobs."""
    p = synthetic_fleet(2)
    stream = ForecastStream.caiso(n_ticks=2, horizon=p.T)
    by_name = RollingHorizonSolver(p, stream, policy="cr2", cap_frac=0.8,
                                   outer=2)
    assert by_name.policy == CR2(cap_frac=0.8, outer=2)
    by_obj = RollingHorizonSolver(p, stream, policy=CR2(cap_frac=0.8,
                                                        outer=2))
    assert by_obj.policy == by_name.policy


def test_adaptive_warm_budget_scales_with_revision_magnitude():
    """ROADMAP adaptive-warm-budgets item: a quiet stream (tiny forecast
    revisions) must spend fewer inner steps per warm tick than the fixed
    budget, at an objective gap < 0.01 pp; a violently revised stream
    keeps the full warm budget."""
    lam = 1.45
    p = synthetic_fleet(6, seed=0)

    def run(adaptive, sigma):
        stream = ForecastStream.caiso(n_ticks=4, horizon=p.T, seed=3,
                                      revision_sigma=sigma)
        rhs = RollingHorizonSolver(p, stream, policy=CR1(lam=lam),
                                   cold_steps=300, warm_steps=120,
                                   adaptive_warm=adaptive)
        objs = {}
        rep = rhs.run(4, on_tick=lambda tk: objs.__setitem__(
            tk.tick, lam * tk.plan.total_penalty_pct
            - tk.plan.carbon_reduction_pct))
        return rep, objs

    fixed, objs_f = run(False, 0.002)
    adapt, objs_a = run(True, 0.002)
    # quiet ticks: every warm budget strictly below the fixed 120
    assert [t.inner_steps for t in fixed.ticks] == [300, 120, 120, 120]
    warm_a = [t.inner_steps for t in adapt.ticks][1:]
    assert all(30 <= s < 120 for s in warm_a), warm_a
    assert adapt.total_inner_steps < fixed.total_inner_steps
    # ...at a per-tick objective gap below 0.01 pp
    gaps = [abs(objs_a[k] - objs_f[k]) for k in objs_f]
    assert max(gaps) < 0.01, gaps
    # violent revisions keep the full budget
    noisy, _ = run(True, 0.5)
    assert [t.inner_steps for t in noisy.ticks][1:] == [120, 120, 120]


def test_adaptive_warm_budget_validates_and_defaults():
    p = synthetic_fleet(2, seed=0)
    stream = ForecastStream.caiso(n_ticks=2, horizon=p.T)
    rhs = RollingHorizonSolver(p, stream, warm_steps=100,
                               adaptive_warm=True)
    assert rhs.warm_steps_min == 25          # warm_steps // 4
    with pytest.raises(ValueError, match="revision_ref"):
        RollingHorizonSolver(p, stream, adaptive_warm=True,
                             revision_ref=0.0)
    # a floor above the warm budget would invert the adaptive scaling
    with pytest.raises(ValueError, match="warm_steps_min"):
        RollingHorizonSolver(p, stream, warm_steps=100,
                             adaptive_warm=True, warm_steps_min=200)
    with pytest.raises(ValueError, match="warm_steps_min"):
        RollingHorizonSolver(p, stream, warm_steps_min=0)


# ---------------------------------------------------------------------------
# Whole-day scan: run_scanned() / api.solve_day — one dispatch per day
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.parametrize("policy,use_kernel", [
    ("cr1", False), ("cr1", True), ("cr2", False), ("cr2", True)])
def test_run_scanned_matches_per_tick_loop(policy, use_kernel):
    """Acceptance: the scanned day reproduces the per-tick step() loop to
    <0.01 pp realized carbon, with identical tick accounting — on both
    the generic engine and the fused al_step kernel."""
    p = synthetic_fleet(8, seed=0)

    def mk():
        return ForecastStream.caiso(n_ticks=4, horizon=p.T, seed=3)

    kw = dict(policy=policy, outer=2, cold_steps=160, warm_steps=40,
              use_kernel=use_kernel)
    loop = RollingHorizonSolver(p, mk(), **kw).run(4)
    scan = RollingHorizonSolver(p, mk(), **kw).run_scanned(4)
    assert abs(scan.realized_reduction_pct
               - loop.realized_reduction_pct) < 0.01
    assert scan.committed.shape == loop.committed.shape == (8, 4)
    np.testing.assert_allclose(scan.committed, loop.committed, atol=5e-3)
    assert [t.inner_steps for t in scan.ticks] \
        == [t.inner_steps for t in loop.ticks]
    assert [t.forecast_mci for t in scan.ticks] \
        == [t.forecast_mci for t in loop.ticks]
    # plan retention contract: full plan on the latest tick only
    assert scan.ticks[-1].plan is not None
    assert all(t.plan is None for t in scan.ticks[:-1])


def test_run_scanned_is_one_dispatch(monkeypatch):
    """The whole day funnels through ONE jitted day-scan call (the tick
    loop is inside lax.scan, not Python)."""
    import repro.core.api as api
    p = synthetic_fleet(4, seed=0)
    stream = ForecastStream.caiso(n_ticks=4, horizon=p.T, seed=1)
    calls = []
    orig = api._DAY.plain
    monkeypatch.setattr(api, "_DAY", api._DAY._replace(
        plain=lambda *a, **k: (calls.append(1), orig(*a, **k))[1]))
    rep = RollingHorizonSolver(p, stream, policy="cr1", cold_steps=60,
                               warm_steps=15).run_scanned(4)
    assert len(calls) == 1
    assert [t.inner_steps for t in rep.ticks] == [60, 15, 15, 15]
    assert rep.total_inner_steps == 60 + 3 * 15


def test_run_scanned_warm_continuation():
    """Mixed schedules work: per-tick steps, then a scanned remainder —
    the scan warm-starts from (and updates) the solver state."""
    p = synthetic_fleet(4, seed=0)
    stream = ForecastStream.caiso(n_ticks=6, horizon=p.T, seed=2)
    rhs = RollingHorizonSolver(p, stream, policy="cr1", cold_steps=80,
                               warm_steps=20)
    rhs.step()
    rhs.step()
    rep = rhs.run_scanned(4)
    assert len(rep.ticks) == 6
    # ticks 2..5 came from the scan, all warm-budgeted
    assert [t.inner_steps for t in rep.ticks] == [80] + [20] * 5
    assert rhs._tick == 6
    # a second scanned day keeps chaining
    st = rep.ticks[-1].plan.state
    assert st is rhs._state


def test_run_scanned_guards():
    p = synthetic_fleet(2, seed=0)

    def mk():
        return ForecastStream.caiso(n_ticks=2, horizon=p.T)

    with pytest.raises(ValueError, match="adaptive_warm"):
        RollingHorizonSolver(p, mk(), adaptive_warm=True).run_scanned(2)
    with pytest.raises(NotImplementedError, match="CR1/CR2"):
        RollingHorizonSolver(p, mk(), policy="cr3", cold_steps=20,
                             warm_steps=5).run_scanned(2)
    with pytest.raises(ValueError, match="n_ticks"):
        RollingHorizonSolver(p, mk()).run_scanned(0)
    # multi-region scanned days run off-mesh too (mesh parity is covered
    # in test_multiregion)
    pr = synthetic_regional_fleet(4, ["CA", "TX"], hours=p.T, seed=0)
    streams = [ForecastStream(actual=np.tile(m, 2), horizon=p.T, seed=i)
               for i, m in enumerate(np.asarray(pr.mci))]
    rep = RollingHorizonSolver(pr, streams, cold_steps=20,
                               warm_steps=5).run_scanned(2)
    assert len(rep.ticks) == 2


def test_solve_day_validates_inputs():
    from repro.core.api import DayResult, solve_day
    p = synthetic_fleet(3, seed=0)
    stack = np.stack([np.asarray(p.mci)] * 2)
    with pytest.raises(TypeError, match="FleetProblem"):
        solve_day(object(), "cr1", stack)
    with pytest.raises(ValueError, match="mci_stack"):
        solve_day(p, "cr1", stack[:, :10])
    # multi-region stacks must match the (R, T) forecast shape
    pr = synthetic_regional_fleet(4, ["CA", "TX"], hours=p.T, seed=0)
    rstack = np.stack([np.asarray(pr.mci)] * 2)
    with pytest.raises(ValueError, match="mci_stack"):
        solve_day(pr, "cr1", rstack[:, :1, :10])
    with pytest.raises(NotImplementedError, match="host-side"):
        solve_day(p, "b1", stack)
    day = solve_day(p, CR1(lam=1.45), stack, cold_steps=40)
    assert isinstance(day, DayResult)
    assert day.committed.shape == (2, 3)
    assert day.inner_steps == (40, 10)
    # warm chaining across days: every tick at the warm budget
    day2 = solve_day(p, CR1(lam=1.45), stack,
                     ctx=SolveContext(warm=day.last.state), cold_steps=40)
    assert day2.inner_steps == (10, 10)
    assert np.isfinite(day2.committed).all()


@pytest.mark.slow
def test_rolling_horizon_multiregion_run_and_scan():
    """Multi-region streaming: one ForecastStream per region, per-region
    committed accounting, and run_scanned parity with the step() loop."""
    p = synthetic_regional_fleet(8, ["CA", "TX"], hours=24, seed=5)

    def mk():
        return [ForecastStream(actual=np.tile(m, 2), horizon=p.T,
                               revision_sigma=0.03, seed=i)
                for i, m in enumerate(np.asarray(p.mci))]

    kw = dict(policy="cr1", cold_steps=120, warm_steps=40)
    loop = RollingHorizonSolver(p, mk(), **kw).run(3)
    tk = loop.ticks[0]
    assert tk.committed_by_region is not None
    assert tk.committed_by_region.shape == (2,)
    assert np.asarray(tk.realized_mci).shape == (2,)
    assert tk.committed_by_region.sum() == pytest.approx(
        tk.committed.sum())
    assert 0 < loop.realized_reduction_pct < 100
    scan = RollingHorizonSolver(p, mk(), **kw).run_scanned(3)
    assert abs(scan.realized_reduction_pct
               - loop.realized_reduction_pct) < 0.01
    np.testing.assert_allclose(scan.committed, loop.committed, atol=5e-3)
    # one stream per region is enforced
    with pytest.raises(ValueError, match="forecast stream"):
        RollingHorizonSolver(p, mk()[:1])


@pytest.mark.slow
def test_rolling_horizon_cr2_carries_multipliers():
    p = synthetic_fleet(4)
    stream = ForecastStream.caiso(n_ticks=3, horizon=p.T, seed=2)
    rhs = RollingHorizonSolver(p, stream, policy="cr2",
                               cold_steps=200, warm_steps=60, outer=2)
    report = rhs.run(3)
    assert report.committed.shape == (4, 3)
    # the CR2 fairness multipliers (one per workload) ride the state
    st = report.ticks[-1].plan.state
    assert st.lam_eq.shape == (4,)
    assert np.isfinite(np.asarray(st.lam_eq)).all()


# ---------------------------------------------------------------------------
# A tick pulls hour 0 and the report sums; the plan stays on the device
# ---------------------------------------------------------------------------
def _tick_stream(donate: bool = False):
    p = synthetic_fleet(6)
    stream = ForecastStream.caiso(n_ticks=3, horizon=p.T, seed=4)
    return p, RollingHorizonSolver(p, stream, policy="cr1", cold_steps=40,
                                   warm_steps=10, use_kernel=False,
                                   donate=donate)


def test_tick_is_one_dispatch_and_pulls_no_plan():
    _, rhs = _tick_stream()
    spans.clear()
    outs = [rhs.step() for _ in range(2)]
    recs = spans.recorded()
    for tick in (0, 1):
        counts = [r.counts for r in recs if r.tick == tick]
        assert sum(c.get("dispatches", 0) for c in counts) == 1
        assert sum(c.get("plan_pulls", 0) for c in counts) == 0
    assert [o.committed.shape for o in outs] == [(6,), (6,)]


def test_plan_read_after_the_tick_is_the_lanes_plan():
    """`plan.D`, read after the tick, is the plan the lane computed, bit
    for bit (the same program run directly), its hour 0 is what the tick
    committed, and reading it is one counted plan pull."""
    p, rhs = _tick_stream()
    out = rhs.step()
    p0 = rhs._window_problem(0, rhs._forecast(0))
    lane = api._TICK["cr1"].plain(
        _jit_view(p0), CR1().lam,
        EngineState.cold(jnp.zeros(p0.usage.shape)), steps=40,
        use_kernel=False, order=None, offsets=(0, p.W))
    spans.clear()
    D = out.plan.D
    (pull,) = spans.recorded()
    assert pull.name == "dr.solve.pull"
    assert pull.counts == {"d2h_bytes": D.nbytes, "plan_pulls": 1}
    np.testing.assert_array_equal(D, np.asarray(lane[0]))
    np.testing.assert_array_equal(D[:, 0], out.committed)
    assert out.plan.D is D and len(spans.recorded()) == 1   # pulled once


def test_donated_tick_leaves_the_previous_plan_readable():
    """With `donate=True` tick k+1 consumes tick k's engine state; the
    plan tick k left on the device is not that state, so it can still be
    read afterwards, and equals the undonated run's plan."""
    plans = {}
    for donate in (True, False):
        _, rhs = _tick_stream(donate)
        first = rhs.step()
        rhs.step()
        assert first.plan.state.x.is_deleted() is donate
        plans[donate] = first.plan.D
    np.testing.assert_array_equal(plans[True], plans[False])


# ---------------------------------------------------------------------------
# The window slides in the solve's program, from a fleet kept on the device
# ---------------------------------------------------------------------------
T_SLIDE = 24            # one day: T + 3 ticks wrap the offset past T
CAMBIUM8 = ["CA", "WA", "AZ", "CO", "TX", "IL", "NY", "FL"]


def _slide_case(kind: str):
    """(fleet, its forecast streams, policy) of a parity case, W <= 512."""
    n = T_SLIDE + 3
    if kind == "cr1_one_region":
        p = synthetic_fleet(40, hours=T_SLIDE, seed=2)
        # an operational cap, so the slide covers `upper` too
        p = dataclasses.replace(p, upper=0.3 * np.asarray(p.usage))
        return p, ForecastStream.caiso(n_ticks=n, horizon=T_SLIDE,
                                       seed=5), CR1(lam=1.45)
    states = CAMBIUM8 if kind == "cr1_eight_regions" else CAMBIUM8[:3]
    p = synthetic_regional_fleet(8 * len(states), states, hours=T_SLIDE,
                                 seed=3)
    streams = ForecastStream.regional(np.tile(np.asarray(p.mci), 3),
                                      horizon=T_SLIDE, seed=6)
    policy = {"cr1_eight_regions": CR1(lam=1.45),
              "cr2_three_regions": CR2(cap_frac=0.78, outer=2),
              "cr3_three_regions": api.CR3(rho=0.05, tax_frac=0.2, outer=2,
                                           clearing_iters=2)}[kind]
    return p, streams, policy


def _slide_solver(p, streams, policy, **kw):
    return RollingHorizonSolver(p, streams, policy=policy, cold_steps=30,
                                warm_steps=8, use_kernel=False, **kw)


@pytest.mark.filterwarnings("ignore:.*fiscal clearing:RuntimeWarning")
@pytest.mark.parametrize("kind", ["cr1_one_region", "cr1_eight_regions",
                                  "cr3_three_regions"])
def test_window_slid_in_the_program_is_the_host_rolled_window(kind):
    """Every tick of a resident run, over T + 3 ticks so the offset wraps
    past T, commits the hour 0 and reports the numbers that a solve of
    the window rolled on the host gives, bit for bit (CPU, no kernel)."""
    p, streams, policy = _slide_case(kind)
    rhs = _slide_solver(p, streams, policy)
    assert rhs._resident is not None
    got = []
    rhs.run(T_SLIDE + 3, on_tick=lambda tk: got.append(tk))
    state = None
    for tk in got:
        p_t = rhs._window_problem(tk.tick, rhs._forecast(tk.tick))
        ref = solve(p_t, policy, ctx=SolveContext(
            warm=state, shift=0 if state is None else 1,
            reset_mu=state is not None, steps=30 if state is None else 8,
            use_kernel=False))
        state = ref.state
        np.testing.assert_array_equal(tk.committed, ref.hour0)
        plan = tk.plan
        for name in ("carbon_reduction_pct", "total_penalty_pct",
                     "preservation_violation", "carbon_base", "iters"):
            assert getattr(plan, name) == getattr(ref, name), (tk.tick, name)
        assert plan.extras.keys() == ref.extras.keys()
        for key, val in ref.extras.items():
            np.testing.assert_array_equal(plan.extras[key], val)
        np.testing.assert_array_equal(np.asarray(plan.state.x),
                                      np.asarray(ref.state.x))


def _one_program_run(policy, knob_bytes: int):
    """A resident run past T ticks: no warm tick compiles after the first
    one (`guard_recompiles`), each tick is one dispatch, and the fleet
    goes up on tick 0 only (`fleet_uploads`); later ticks send the
    forecast, the knob (`knob_bytes`) and the offset."""
    p, stream, _ = _slide_case("cr1_one_region")
    rhs = _slide_solver(p, stream, policy, guard_recompiles=True)
    spans.clear()
    rhs.run(T_SLIDE + 3)
    sends = [r for r in spans.recorded() if r.name == "dr.solve.dispatch"]
    assert [r.tick for r in sends] == list(range(T_SLIDE + 3))
    assert [r.counts["dispatches"] for r in sends] == [1] * (T_SLIDE + 3)
    assert [r.counts.get("fleet_uploads", 0) for r in sends] \
        == [1] + [0] * (T_SLIDE + 2)
    # the forecast (T,) as float32, the knob, the offset as int32
    assert {r.counts["h2d_bytes"] for r in sends[1:]} \
        == {4 * T_SLIDE + knob_bytes + 4}
    assert sends[0].counts["h2d_bytes"] > 4 * p.W * T_SLIDE


def test_window_offsets_share_one_program_and_the_fleet_goes_up_once():
    """CR1's ticks send λ as float32 (`_one_program_run`)."""
    _one_program_run(CR1(lam=1.45), 4)


def test_cr2_window_offsets_share_one_program_and_the_fleet_goes_up_once():
    """CR2's ticks send no knob: the fleet's equal-cap curtailment at its
    cap_frac (`cr2_cap`) goes up with the fleet, on tick 0
    (`_one_program_run`)."""
    _one_program_run(CR2(outer=2), 0)


def test_resident_cr2_ticks_match_host_slid_ticks():
    """CR2 on the resident fleet, four ticks of a three-region fleet
    whose offsets wrap past T: every tick commits what a solve of the
    window rolled on the host commits (its fairness references made on
    the host), and the plans report the same numbers, to the float32
    rounding of the references. (A CR2 tick magnifies a difference of
    its references' last bits: over T ticks such a gap grows to the
    commitments' size, so the run is kept short.)"""
    p, streams, policy = _slide_case("cr2_three_regions")
    rhs = _slide_solver(p, streams, policy)
    assert rhs._resident is not None
    rhs._tick = T_SLIDE - 2
    got = []
    rhs.run(4, on_tick=lambda tk: got.append(tk))
    assert [tk.tick % T_SLIDE for tk in got] == [T_SLIDE - 2, T_SLIDE - 1,
                                                 0, 1]
    state = None
    for tk in got:
        p_t = rhs._window_problem(tk.tick, rhs._forecast(tk.tick))
        ref = solve(p_t, policy, ctx=SolveContext(
            warm=state, shift=0 if state is None else 1,
            reset_mu=state is not None, steps=30 if state is None else 8,
            use_kernel=False))
        state = ref.state
        scale = np.abs(ref.hour0).max()
        np.testing.assert_allclose(tk.committed, ref.hour0, rtol=0,
                                   atol=1e-5 * scale, err_msg=tk.tick)
        for name in ("carbon_reduction_pct", "total_penalty_pct"):
            assert abs(getattr(tk.plan, name) - getattr(ref, name)) < 1e-5, \
                (tk.tick, name)
        assert tk.plan.iters == ref.iters


def test_window_references_are_the_host_references_of_every_offset():
    """The fairness references a sliding CR2 tick makes in its program,
    for every hour offset 0..T-1, are `cr2_reference_fleet` of the window
    rolled on the host to float32 rounding: the tick slides the whole
    fleet's curtailment usage - cap_frac·E, made on the host in float64
    (`cr2_cap`). Made in float32 (the day scan's way) it is off by more
    than that."""
    p, _, _ = _slide_case("cr2_three_regions")
    cap = 0.78

    @jax.jit
    def refs(pj, d_cap, window):
        slid = api._slide(pj, window)
        return (api._cr2_window_refs(slid, d_cap, False, window),
                api._cr2_window_refs(slid, cap, False))

    ulp = 2.0 ** -23
    for w in range(T_SLIDE):
        host = cr2_reference_fleet(dataclasses.replace(
            p, usage=np.roll(p.usage, -w, axis=1),
            jobs=np.roll(p.jobs, -w, axis=1)), cap)
        exact, f32 = (np.asarray(a) for a in
                      refs(_jit_view(p), cr2_cap(p, cap), np.int32(w)))
        np.testing.assert_allclose(exact, host, rtol=4 * ulp, atol=0,
                                   err_msg=w)
        assert not np.allclose(f32, host, rtol=4 * ulp, atol=0), w


@pytest.mark.parametrize("policy, rounds", [(CR1(), 1), (CR2(outer=3), 3)])
def test_engine_ticks_count_their_al_rounds_and_steps(policy, rounds):
    """Each CR1/CR2 tick counts on `dr.solve` the AL multiplier rounds it
    ran (`al_rounds`) and its projected-Adam steps (`al_steps`: steps ×
    rounds), cold and warm."""
    p, stream, _ = _slide_case("cr1_one_region")
    rhs = _slide_solver(p, stream, policy)
    spans.clear()
    rhs.run(3)
    solves = [r for r in spans.recorded() if r.name == "dr.solve"]
    assert [r.counts["al_rounds"] for r in solves] == [rounds] * 3
    assert [r.counts["al_steps"] for r in solves] \
        == [30 * rounds, 8 * rounds, 8 * rounds]


def test_window_and_fleet_are_refused_where_no_lane_slides():
    """A lane that cannot slide in its program refuses the offset rather
    than solving the unslid hours; the streaming solver keeps those on
    the host roll."""
    p = synthetic_fleet(4, seed=0)
    with pytest.raises(NotImplementedError, match="window"):
        api.sweep(p, [CR1(lam=1.0), CR1(lam=2.0)],
                  ctx=SolveContext(window=3))
    stream = ForecastStream.caiso(n_ticks=2, horizon=p.T)
    solver = RollingHorizonSolver(p, stream, policy="b1")
    assert solver._resident is None
    with pytest.raises(NotImplementedError, match="window"):
        solve(p, "b1", ctx=SolveContext(window=3))
    # CR2 slides: the solver keeps its fleet resident, and its solo lane
    # takes the offset, solving the hours it names
    for policy in ("cr1", "cr2"):
        assert RollingHorizonSolver(p, stream, policy=policy)._resident \
            is not None
    slid = solve(p, "cr2", ctx=SolveContext(window=3, steps=20,
                                            use_kernel=False))
    rolled = solve(dataclasses.replace(
        p, usage=np.roll(p.usage, -3, axis=1),
        jobs=np.roll(p.jobs, -3, axis=1)), "cr2",
        ctx=SolveContext(steps=20, use_kernel=False))
    np.testing.assert_allclose(slid.hour0, rolled.hour0, rtol=0,
                               atol=1e-4 * np.abs(rolled.hour0).max())
