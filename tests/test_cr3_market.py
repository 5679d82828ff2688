"""CR3's on-device market (`api._cr3_market`) against the plain fiscal
reference (`bench/references/cr3.py`), and the loop's own contracts.

The program clears every region's Eq.-6 market in one `lax.while_loop`
on the device; the reference clears each region on its own, on the host,
from the policy's statement. A warm-started chain of ticks amplifies
float32 rounding (two float implementations part by more each tick, and
a region whose balance sits within rounding of its taxes can clear in a
different number of rounds), so every tick is replayed by the reference
from the program's own incoming plan and multipliers: each tick's market
is compared, not the drift of the chain.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys
import warnings

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

from harness import spec, traffic  # noqa: E402

from repro.analysis import recompile_guard  # noqa: E402
from repro.core import api  # noqa: E402
from repro.core.api import CR3, SolveContext, solve, sweep  # noqa: E402
from repro.core.carbon import regional_traces  # noqa: E402
from repro.core.fleet_solver import (regional_fleet,  # noqa: E402
                                     synthetic_fleet)
from repro.core.regional import region_layout as _region_layout  # noqa: E402
from repro.obs import spans  # noqa: E402

COLD, WARM, OUTER, TICKS = 60, 20, 2, 2
FOUR = ["CA", "TX", "NY", "FL"]

# Tolerances, program against the float32 reference on the same tick:
#: ρ per region. The price moves by ratios of per-region sums of float32
#: plans, which the two compute apart (device double-float against host
#: float64); the two part by 1e-6 at most here. The bfloat16 reference
#: misses by ~1e-2.
RHO_RTOL = 1e-4
#: The plan, row by row: the 90th percentile over rows of each row's
#: largest gap, in parts of the plan's largest entry. A best response
#: need not be unique (hinged batch penalties): a row between two about
#: equally cheap plans can land on either after a rounding difference
#: (one row in 16 here), so the rows are held at a percentile. Rounds of
#: OUTER x COLD projected Adam steps leave the others within ~1e-3; the
#: bfloat16 reference misses by 0.1-0.4.
PLAN_TOL = 5e-3
#: The fiscal deficit of an unbalanced market, relative: a difference of
#: per-region sums of the plans, which part by ~2e-4 here.
DEFICIT_RTOL = 1e-2


def _case(states, policy_args, seed=7):
    """A reduced Cambium fleet over `states` (one region is the plain
    single-region path), its forecasts, and a streaming solver of the
    `cr3_ticks` traffic with `policy_args` at small step budgets."""
    cfg = json.loads((ROOT / "bench/configs/cambium_r8_w100k.json")
                     .read_text())
    cfg["fleet"]["args"].update(num=16 * len(states), states=states)
    cfg["signal"]["regions"] = [r for r in cfg["signal"]["regions"]
                                if r["label"] in states]
    tr = json.loads((ROOT / "bench/traffic/cr3_ticks.json").read_text())
    args = {**tr["policy_args"], "outer": OUTER, **policy_args}
    tr["policy_args"] = args
    tr["algorithm"].update(args)
    tr["solver"].update(cold_steps=COLD, warm_steps=WARM)
    p = traffic.build_fleet(cfg, seed)
    actual, fc = traffic.make_forecasts(cfg, tr, p.T, seed)
    return p, tr, fc, traffic.make_solver(p, tr, actual, fc)


def _ticks(states, policy_args, dtype="float32"):
    """Stream TICKS ticks through the program and replay each tick's
    markets with the reference from the program's incoming state.
    Yields (program plan, reference outcomes per region, reference plan,
    rounds the program ran)."""
    p, tr, fc, solver = _case(states, policy_args)
    ref = spec.reference("cr3").Replay(traffic.fleet_arrays(p),
                                       np.arange(p.W), tr["algorithm"],
                                       dtype)
    for t in range(TICKS):
        prev = solver._state
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            plan = solver.step().plan
        steps = COLD if t == 0 else WARM
        if prev is None:
            x_in, lam_in = np.zeros(p.usage.shape), np.zeros(p.W)
        else:
            x_in = np.roll(np.asarray(prev.x), -1, axis=1)
            x_in[:, -1] = 0.0
            lam_in = np.asarray(prev.lam_in)
        want = np.zeros(p.usage.shape)
        outcomes = []
        for r, idx in enumerate(ref.members):
            x, _lam, out = ref.market(
                r, ref.jnp.asarray(x_in[idx], ref.dt),
                ref.jnp.asarray(lam_in[idx], ref.dt), t, fc[t][r], steps)
            want[idx] = np.asarray(x, np.float64)
            outcomes.append(out)
        yield plan, np.asarray(outcomes), want, plan.iters // (steps * OUTER)


def _gaps(plan, outcomes, want, rounds) -> dict:
    rho, paid, collected, ref_rounds = outcomes.T
    deficit = np.where(paid > collected + 1e-9, paid - collected, 0.0).sum()
    return {
        "rounds": (rounds, int(ref_rounds.max())),
        "balanced": (plan.balanced, bool((paid <= collected + 1e-9).all())),
        "rho": float(np.abs(np.atleast_1d(plan.extras["rho"]) / rho
                            - 1.0).max()),
        "plan": float(np.percentile(np.abs(plan.D - want).max(axis=1), 90)
                      / np.abs(want).max()),
        "deficit": abs(plan.fiscal_deficit - deficit)
        / max(deficit, 1e-30),
    }


def _within(g: dict) -> bool:
    return (g["rounds"][0] == g["rounds"][1]
            and g["balanced"][0] == g["balanced"][1]
            and g["rho"] <= RHO_RTOL and g["plan"] <= PLAN_TOL
            and (g["balanced"][0] or g["deficit"] <= DEFICIT_RTOL))


CASES = {
    # tick 0 clears in several rounds, tick 1 in one
    "one_region": (["CA"], {"tax_frac": 0.3}),
    "four_regions": (FOUR, {"tax_frac": 0.2}),
    # one price cut cannot close the deficits: clearing_iters exhausted
    "exhausted": (FOUR, {"rho": 0.05, "clearing_iters": 1}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_program_market_matches_reference(case):
    states, args = CASES[case]
    gaps = [_gaps(*tick) for tick in _ticks(states, args)]
    for g in gaps:
        assert _within(g), gaps
    rounds = [g["rounds"][0] for g in gaps]
    balanced = [g["balanced"][0] for g in gaps]
    if case == "exhausted":
        assert rounds == [2] * TICKS and not any(balanced)
    else:
        assert all(balanced) and max(rounds) > 1
    if case == "one_region":
        assert 1 in rounds


def test_bfloat16_reference_misses_the_tolerances():
    gaps = [_gaps(*tick)
            for tick in _ticks(*CASES["one_region"], dtype="bfloat16")]
    assert not all(_within(g) for g in gaps), gaps


def _four_fleets(W=12):
    mcis, _ = regional_traces(FOUR, 2050, hours=48)
    return [synthetic_fleet(W, seed=3 + r) for r in range(4)], mcis


def test_multiregion_market_equals_each_region_alone():
    """With no migration each region's rows follow exactly its own
    market: a region's Eq.-6 sums are the same reduction alone or in the
    fleet, and regions that cleared keep their rows through later
    rounds. Regions here clear in different numbers of rounds."""
    fleets, mcis = _four_fleets()
    joint = regional_fleet(fleets, mcis)
    ctx = SolveContext(steps=100)
    policy = CR3(outer=2)
    res = solve(joint, policy, ctx=ctx)
    region = np.asarray(joint.region)
    alone = [solve(dataclasses.replace(f, mci=mcis[r]), policy, ctx=ctx)
             for r, f in enumerate(fleets)]
    assert len({a.iters for a in alone}) > 1
    assert res.iters == max(a.iters for a in alone)
    for r, a in enumerate(alone):
        np.testing.assert_array_equal(res.D[region == r], a.D)
        np.testing.assert_array_equal(
            np.asarray(res.state.lam_in)[region == r],
            np.asarray(a.state.lam_in))
        assert res.extras["rho"][r] == a.extras["rho"]


def test_rows_out_of_region_order_clear_the_same_markets():
    """A fleet whose regions' rows are interleaved sums each region's
    rows as one run, in their order (`regional.region_layout`): the same
    markets, bit for bit, row for row."""
    fleets, mcis = _four_fleets()
    joint = regional_fleet(fleets, mcis)
    # round robin over the regions, each region's rows in their order
    perm = np.arange(joint.W).reshape(joint.R, -1).T.ravel()
    rows = ("usage", "entitlement", "k", "rts_coeffs", "betas", "x2_kind",
            "jobs", "is_batch", "region")
    mixed = dataclasses.replace(
        joint, names=None,
        **{f: np.asarray(getattr(joint, f))[perm] for f in rows})
    ctx = SolveContext(steps=100)
    a = solve(joint, CR3(outer=2), ctx=ctx)
    b = solve(mixed, CR3(outer=2), ctx=ctx)
    np.testing.assert_array_equal(b.D, a.D[perm])
    np.testing.assert_array_equal(b.extras["rho"], a.extras["rho"])
    assert b.iters == a.iters


def test_balanced_regions_pay_no_more_rebates_than_their_taxes():
    fleets, mcis = _four_fleets()
    joint = regional_fleet(fleets, mcis)
    policy = CR3(outer=2)
    res = solve(joint, policy, ctx=SolveContext(steps=100))
    assert res.balanced
    region = np.asarray(joint.region)
    D = np.asarray(res.D, np.float64)
    E = np.asarray(joint.entitlement, np.float64)
    for r in range(joint.R):
        paid = res.extras["rho"][r] * (D[region == r] @ mcis[r]).sum()
        taxes = policy.tax_frac * E[region == r].sum()
        assert paid <= taxes + 1e-9


def _fiscal_case(W=3000, R=3, seed=0):
    """Plans of uneven regions (rows sorted by region), float64 mci and
    prices, and the program's layout of the rows."""
    rng = np.random.default_rng(seed)
    region = np.sort(rng.integers(0, R, W))
    D = (rng.normal(size=(W, 48)) * rng.uniform(0.1, 30.0, (W, 1))
         ).astype(np.float32)
    mci = rng.uniform(100.0, 600.0, (R, 48))
    rho = rng.uniform(0.005, 0.02, R)
    return D, region, mci, rho, _region_layout(region, R)


def _rebates64(D, region, mci, rho):
    """ρ_r·Σ_{i∈r}⟨d_i, mci_r⟩, summed exactly (math.fsum) in float64."""
    import math
    D = np.asarray(D, np.float64)
    return np.array([r_ * math.fsum((D[region == r] * m).ravel())
                     for r, (m, r_) in enumerate(zip(mci, rho))])


def test_device_rebates_sum_as_float64_does():
    """The loop's rebates (`api._cr3_rebates`, double-float from float32
    operations) sit within 1e-13 of the exact float64 sums, in parts of
    the sums of |terms|; plain float32 sums of the same plans miss by
    about 1e-7."""
    from repro.core import dfloat
    D, region, mci, rho, (order, offsets) = _fiscal_case()
    got = jax.jit(api._cr3_rebates, static_argnums=(3, 4))(
        D, dfloat.split(rho), dfloat.split(mci), order, offsets)
    got = np.asarray(got[0], np.float64) + np.asarray(got[1], np.float64)
    want = _rebates64(D, region, mci, rho)
    scale = _rebates64(np.abs(D), region, mci, rho)
    assert np.abs(got - want).max() <= 1e-13 * scale.min()
    f32 = np.array([np.float32(r_) * (D[region == r] * m.astype(np.float32)
                                      ).sum(dtype=np.float32)
                    for r, (m, r_) in enumerate(zip(mci, rho))])
    assert np.abs(f32 - want).max() > 1e-9 * scale.min()


@pytest.mark.parametrize("side", [-1, 1])
def test_deficit_test_decides_as_float64_does(side):
    """Taxes set 1e-12 (relative) to either side of the rebates: the
    device's deficit test (`dfloat.gt` against collected + 1e-9) says
    what float64 says, where float32 sums cannot tell the two apart."""
    from repro.core import dfloat
    D, region, mci, rho, (order, offsets) = _fiscal_case(seed=1)
    paid = _rebates64(D, region, mci, rho)
    collected = paid - side * 1e-12 * np.abs(paid) - 1e-9
    _mci, bar, _c9 = api._cr3_fiscal(
        dataclasses.replace(synthetic_fleet(4), mci=mci), collected)
    dev = jax.jit(api._cr3_rebates, static_argnums=(3, 4))(
        D, dfloat.split(rho), _mci, order, offsets)
    deficit = np.asarray(jax.jit(dfloat.gt)(dev, bar))
    assert (deficit == (paid > collected + 1e-9)).all()
    assert deficit.all() == (side == 1)


@pytest.mark.parametrize("regions", [1, 4])
def test_more_rounds_than_the_last_tick_compile_nothing(regions):
    """A warm tick is one dispatch and one pull whatever its round count:
    a tick that clears in several rounds after one that cleared in one
    reuses the same program."""
    if regions == 1:
        p = synthetic_fleet(16, seed=2)
    else:
        p = regional_fleet(*_four_fleets(4))
    cold = solve(p, CR3(outer=2), ctx=SolveContext(steps=40))
    ctx = SolveContext(steps=20, warm=cold.state, shift=1, reset_mu=True)
    one = solve(p, CR3(outer=2, tax_frac=0.1), ctx=ctx)
    n_programs = api._cr3_cleared._cache_size()
    spans.clear()
    with recompile_guard(0), warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        more = solve(p, CR3(outer=2, rho=1.0), ctx=ctx)
    assert more.iters > one.iters
    assert api._cr3_cleared._cache_size() == n_programs
    names = [r.name for r in spans.recorded()]
    assert names.count("dr.solve.dispatch") == names.count(
        "dr.solve.pull") == 1
    (root,) = [r for r in spans.recorded() if r.name == "dr.solve"]
    assert root.counts["clear_rounds"] == more.iters // 40


def test_sweep_lanes_with_their_own_round_caps_match_solo_solves():
    """The sweep runs every lane's market in one program, each lane with
    its own ρ, tax and `clearing_iters`; each lane follows its solo
    solve: the same rounds and balance, and ρ, carbon and penalty to
    vmap rounding amplified through the warm rounds (the tolerances of
    `test_api`'s CR3 sweep test)."""
    p = synthetic_fleet(16, seed=2)
    pols = [CR3(rho=1.0, outer=2, clearing_iters=n) for n in (0, 3)]
    ctx = SolveContext(steps=40)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        spans.clear()
        got = sweep(p, pols, ctx=ctx)
        assert [r.name for r in spans.recorded()].count(
            "dr.solve.dispatch") == 1
        solo = [solve(p, pl, ctx=ctx) for pl in pols]
    assert got[0].iters != got[1].iters
    for g, s in zip(got, solo):
        assert g.iters == s.iters
        assert g.balanced == s.balanced
        np.testing.assert_allclose(g.extras["rho"], s.extras["rho"],
                                   rtol=1e-3)
        assert abs(g.carbon_reduction_pct - s.carbon_reduction_pct) < 0.05
        assert abs(g.total_penalty_pct - s.total_penalty_pct) < 0.05
