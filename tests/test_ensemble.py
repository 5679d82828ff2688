"""Ensemble evaluation (`repro.core.ensemble`): batched-vs-loop parity,
the risk-report layer, and the batched rolling-horizon ensemble."""
import dataclasses

import numpy as np
import pytest

from repro.core.api import B1, CR1, CR2, CR3, SolveContext, ensemble, solve
from repro.core.ensemble import (comparison_table, compare_policies,
                                 evaluate_ensemble, run_streaming_ensemble)
from repro.core.fleet_solver import synthetic_fleet
from repro.core.scenario import (CambiumMix, DuckPerturb, FleetJitter,
                                 FlexMixShift, ForecastRegime,
                                 RenewableDrought, ScenarioStack,
                                 resolve_scenarios)
from repro.core.streaming import RollingHorizonSolver


@pytest.fixture(scope="module")
def fleet():
    return synthetic_fleet(6, seed=1)


@pytest.fixture(scope="module")
def mixed_stack(fleet):
    """MCI + fleet overlays in one stack (exercises the vmapped problem
    fields jointly)."""
    return resolve_scenarios(
        [DuckPerturb(n_scenarios=2, seed=1),
         FleetJitter(n_scenarios=2, seed=2),
         FlexMixShift(n_scenarios=2, seed=3)], fleet)


# ---------------------------------------------------------------------------
# Batched lane == sequential api.solve loop (the core parity contract)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", [CR1(lam=1.4),
                                    CR2(cap_frac=0.8, outer=2)])
def test_batched_matches_solve_loop(policy, fleet, mixed_stack):
    ctx = SolveContext(steps=120)
    got = evaluate_ensemble(fleet, policy, mixed_stack, ctx=ctx)
    ref = evaluate_ensemble(fleet, policy, mixed_stack, ctx=ctx,
                            batched=False)
    assert got.batched and not ref.batched
    assert got.D.shape == (mixed_stack.S, fleet.W, fleet.T)
    assert np.abs(got.carbon_reduction_pct
                  - ref.carbon_reduction_pct).max() < 0.01
    assert np.abs(got.total_penalty_pct
                  - ref.total_penalty_pct).max() < 0.01
    np.testing.assert_allclose(got.D, ref.D, atol=1e-3)
    # per-scenario loop results match a direct api.solve of the
    # materialized scenario problem exactly
    s = 2
    direct = solve(mixed_stack.problem(fleet, s), policy, ctx=ctx)
    assert ref.carbon_reduction_pct[s] == direct.carbon_reduction_pct
    np.testing.assert_array_equal(ref.D[s], direct.D)


def test_cr2_jobs_only_overlay_recomputes_references(fleet):
    """Regression: `cr2_reference_fleet` depends on jobs (Table-IV
    features), so a jobs-only overlay must get per-scenario fairness
    targets in the batched lane — sharing the base reference broke the
    <0.01 pp parity contract by ~0.03 pp."""
    jobs = np.stack([0.5 * np.asarray(fleet.jobs),
                     2.0 * np.asarray(fleet.jobs)])
    stack = ScenarioStack(jobs=jobs)
    ctx = SolveContext(steps=100)
    pol = CR2(cap_frac=0.8, outer=2)
    got = evaluate_ensemble(fleet, pol, stack, ctx=ctx)
    ref = evaluate_ensemble(fleet, pol, stack, ctx=ctx, batched=False)
    assert np.abs(got.carbon_reduction_pct
                  - ref.carbon_reduction_pct).max() < 0.01
    assert np.abs(got.total_penalty_pct
                  - ref.total_penalty_pct).max() < 0.01


def test_fallback_policies_loop_with_identical_semantics(fleet):
    stack = DuckPerturb(n_scenarios=2, seed=5).generate(fleet)
    ctx = SolveContext(steps=60)
    for policy in (B1(F=0.8), CR3(outer=1, clearing_iters=1)):
        res = evaluate_ensemble(fleet, policy, stack, ctx=ctx)
        assert not res.batched
        for s in range(stack.S):
            direct = solve(stack.problem(fleet, s), policy, ctx=ctx)
            np.testing.assert_array_equal(res.D[s], direct.D)
            assert res.extras[s] == direct.extras


def test_batched_flag_forces_and_rejects(fleet):
    stack = DuckPerturb(n_scenarios=2, seed=0).generate(fleet)
    with pytest.raises(ValueError, match="no batched ensemble lane"):
        evaluate_ensemble(fleet, B1(), stack, batched=True)
    with pytest.raises(ValueError, match="no batched ensemble lane"):
        evaluate_ensemble(fleet, CR1(), stack, batched=True,
                          ctx=SolveContext(steps=30, shift=1))
    # api.ensemble is the same entry point
    a = ensemble(fleet, CR1(lam=1.3), stack, ctx=SolveContext(steps=60))
    b = evaluate_ensemble(fleet, CR1(lam=1.3), stack,
                          ctx=SolveContext(steps=60))
    np.testing.assert_allclose(a.D, b.D, atol=1e-12)


def test_ensemble_determinism(fleet):
    """Same generator spec + seed -> bitwise-identical ensemble outcomes."""
    ctx = SolveContext(steps=60)
    a = evaluate_ensemble(fleet, CR1(lam=1.45),
                          CambiumMix(n_scenarios=3, seed=9), ctx=ctx)
    b = evaluate_ensemble(fleet, CR1(lam=1.45),
                          CambiumMix(n_scenarios=3, seed=9), ctx=ctx)
    np.testing.assert_array_equal(a.D, b.D)
    np.testing.assert_array_equal(a.carbon_reduction_pct,
                                  b.carbon_reduction_pct)
    assert a.labels == b.labels


# ---------------------------------------------------------------------------
# Risk layer
# ---------------------------------------------------------------------------
def test_report_stats_are_coherent(fleet, mixed_stack):
    res = evaluate_ensemble(fleet, CR1(lam=1.4), mixed_stack,
                            ctx=SolveContext(steps=100))
    rep = res.report(slo_frac=0.05, cvar_alpha=0.25)
    q = rep.carbon_quantiles
    assert q["p5"] <= q["p25"] <= q["p50"] <= q["p75"] <= q["p95"]
    # CVaR of the bad tail bounds the median from the bad side
    assert rep.carbon_cvar <= q["p50"] + 1e-9
    assert rep.penalty_cvar >= rep.penalty_quantiles["p50"] - 1e-9
    assert 0.0 < rep.jain_min <= rep.jain_quantiles["p50"] <= 1.0 + 1e-9
    assert rep.maxmin_median >= 1.0
    assert 0.0 <= rep.slo_violation_prob <= 1.0
    assert rep.workload_slo_prob.shape == (fleet.W,)
    assert (rep.workload_slo_prob >= 0).all()
    assert (rep.workload_slo_prob <= 1).all()
    # any-workload breach prob dominates each per-workload prob
    assert rep.slo_violation_prob >= rep.workload_slo_prob.max() - 1e-9
    assert len(rep.worst_scenarios) == max(1, int(np.ceil(0.25 * res.S)))
    assert set(rep.worst_scenarios) <= set(res.labels)
    assert any("CVaR" in ln for ln in rep.lines())
    d = rep.as_dict()
    assert isinstance(d["workload_slo_prob"], list)


def test_slo_threshold_moves_violation_prob(fleet, mixed_stack):
    res = evaluate_ensemble(fleet, CR1(lam=1.4), mixed_stack,
                            ctx=SolveContext(steps=100))
    loose = res.report(slo_frac=1e6).slo_violation_prob
    tight = res.report(slo_frac=1e-9).slo_violation_prob
    assert loose == 0.0
    assert tight >= res.report(slo_frac=0.05).slo_violation_prob


def test_compare_policies_table(fleet):
    stack = DuckPerturb(n_scenarios=3, seed=2).generate(fleet)
    reps = compare_policies(fleet, [CR1(lam=1.4), B1(F=0.8)], stack,
                            ctx=SolveContext(steps=60))
    assert set(reps) == {"cr1", "b1"}
    table = comparison_table(reps)
    assert len(table) == 4                     # header + rule + 2 rows
    assert "cr1" in table[2] and "b1" in table[3]
    # duplicate families get disambiguated keys
    reps2 = compare_policies(fleet, [CR1(lam=1.2), CR1(lam=1.6)], stack,
                             ctx=SolveContext(steps=60))
    assert set(reps2) == {"cr1", "cr1#1"}


# ---------------------------------------------------------------------------
# Rolling-horizon ensemble (batched warm-started ticks)
# ---------------------------------------------------------------------------
def test_streaming_ensemble_matches_solo_controllers(fleet):
    streams = ForecastRegime(n_scenarios=2, seed=5,
                             sigma=(0.02, 0.06)).streams(fleet, n_ticks=3)
    rep = run_streaming_ensemble(fleet, CR1(lam=1.45), streams, n_ticks=3,
                                 cold_steps=200, warm_steps=60)
    assert rep.batched
    assert rep.committed.shape == (2, fleet.W, 3)
    assert rep.total_inner_steps == 200 + 2 * 60
    for s, st in enumerate(streams):
        solo = RollingHorizonSolver(fleet, st, policy=CR1(lam=1.45),
                                    cold_steps=200, warm_steps=60).run(3)
        np.testing.assert_allclose(rep.committed[s], solo.committed,
                                   atol=1e-4)
        assert abs(rep.realized_reduction_pct[s]
                   - solo.realized_reduction_pct) < 0.01
    risk = rep.risk(cvar_alpha=0.5)
    assert risk["cvar50"] <= risk["p50"] + 1e-9
    assert np.isfinite(risk["mean"])


def test_streaming_ensemble_cr2_and_fallback(fleet):
    streams = ForecastRegime(n_scenarios=2, seed=1).streams(fleet,
                                                            n_ticks=2)
    rep2 = run_streaming_ensemble(fleet, CR2(cap_frac=0.8, outer=2),
                                  streams, n_ticks=2, cold_steps=80,
                                  warm_steps=40)
    assert rep2.batched
    assert rep2.total_inner_steps == (80 + 40) * 2       # steps * outer
    # closed-form baseline rides the sequential fallback
    repb = run_streaming_ensemble(fleet, B1(F=0.8), streams, n_ticks=2)
    assert not repb.batched
    assert repb.committed.shape == (2, fleet.W, 2)


def test_streaming_ensemble_validates_inputs(fleet):
    streams = ForecastRegime(n_scenarios=2, seed=0).streams(fleet,
                                                            n_ticks=2)
    with pytest.raises(ValueError, match=">= 1 stream"):
        run_streaming_ensemble(fleet, CR1(), [])
    with pytest.raises(ValueError, match="n_ticks"):
        run_streaming_ensemble(fleet, CR1(), streams, n_ticks=10 ** 6)
    bad = ForecastRegime(n_scenarios=1, seed=0).streams(
        synthetic_fleet(2, seed=0, hours=24), n_ticks=2)
    with pytest.raises(ValueError, match="horizon"):
        run_streaming_ensemble(fleet, CR1(), bad)


# ---------------------------------------------------------------------------
# Multi-region ensembles (ISSUE 8): batched lane + streaming groups
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def regional():
    import dataclasses

    from repro.core.fleet_solver import synthetic_regional_fleet
    del dataclasses
    return synthetic_regional_fleet(8, ["CA", "TX"], hours=24, seed=2)


def test_regional_divergence_batched_matches_loop(regional):
    """`RegionalDivergence` through the one-dispatch batched lane — with
    the per-scenario migration post-stage credited — matches the
    sequential api.solve loop to <0.01 pp."""
    from repro.core.scenario import RegionalDivergence
    gen = RegionalDivergence(n_scenarios=3, seed=0)
    ctx = SolveContext(steps=120)
    got = evaluate_ensemble(regional, CR1(lam=1.45), gen, ctx=ctx)
    ref = evaluate_ensemble(regional, CR1(lam=1.45), gen, ctx=ctx,
                            batched=False)
    assert got.batched and not ref.batched
    assert got.D.shape == (3, regional.W, regional.T)
    assert np.abs(got.carbon_reduction_pct
                  - ref.carbon_reduction_pct).max() < 0.01
    assert np.abs(got.total_penalty_pct
                  - ref.total_penalty_pct).max() < 0.01
    # the migration credit is really in there: every scenario's extras
    # carry a per-scenario plan on this positive-bandwidth topology
    assert all("migration" in e for e in got.extras)


def test_streaming_ensemble_multiregion_matches_solo(regional):
    """Multi-region streaming ensembles: S groups of R streams batch as
    (S, R, T) forecast stacks through the one-dispatch lane and match
    per-scenario solo RollingHorizonSolver runs to <0.01 pp."""
    regime = ForecastRegime(n_scenarios=2, seed=0, sigma=(0.02, 0.05))
    rep = run_streaming_ensemble(regional, CR1(lam=1.45), regime,
                                 n_ticks=3, cold_steps=150, warm_steps=50)
    assert rep.batched
    assert rep.committed.shape == (2, regional.W, 3)
    for g, ens_red in zip(regime.streams(regional, n_ticks=3),
                          rep.realized_reduction_pct):
        assert len(g) == regional.R
        solo = RollingHorizonSolver(regional, g, policy=CR1(lam=1.45),
                                    cold_steps=150, warm_steps=50).run(3)
        assert abs(ens_red - solo.realized_reduction_pct) < 0.01


def test_streaming_ensemble_multiregion_validates_groups(regional):
    full = ForecastRegime(n_scenarios=1, seed=0).streams(regional,
                                                         n_ticks=2)
    short = [g[:1] for g in full]              # one stream, two regions
    with pytest.raises(ValueError, match="per region"):
        run_streaming_ensemble(regional, CR1(), short, n_ticks=2)


@pytest.mark.parametrize("policy", [CR1(), CR2(outer=1)], ids=["cr1", "cr2"])
def test_sharded_ensemble_lane_runs_the_fused_inner_loop(policy, monkeypatch):
    """With the kernel on, the ensemble lane on a mesh builds the fused
    al_step inner loop, as every other CR1/CR2 lane does, and plans as
    the solo ensemble lane does."""
    from repro.core import api
    from repro.launch.mesh import make_fleet_mesh

    built = []
    fused = api._al_fused_inner
    monkeypatch.setattr(api, "_al_fused_inner", lambda *a, **k: (
        built.append(a[1]), fused(*a, **k))[1])
    p = synthetic_fleet(8, seed=0)
    stack = resolve_scenarios(DuckPerturb(n_scenarios=2, seed=1), p)
    ctx = SolveContext(steps=3, use_kernel=True)
    mesh = evaluate_ensemble(p, policy, stack, ctx=dataclasses.replace(
        ctx, mesh=make_fleet_mesh(1)))
    assert mesh.batched and built == [policy.name]
    solo = evaluate_ensemble(p, policy, stack, ctx=ctx)
    np.testing.assert_allclose(mesh.D, solo.D, rtol=0, atol=1e-5)
