"""A solve's report from device sums (`fleet_solver.report_sums`, double-
float) against the float64 host arithmetic it replaced.

The reference below is that arithmetic, kept here as plain numpy: carbon
as Σ_w ⟨D_w, mci_region(w)⟩ over the same sum of the usage, the
penalties' total over the entitlements', and the largest |day total| of
a batch row — every sum in float64."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api
from repro.core.api import B1, CR1, SolveContext, sweep
from repro.core.engine import EngineState
from repro.core.fleet_solver import (_jit_view, _report, fleet_penalties,
                                     report_layout, synthetic_fleet,
                                     synthetic_regional_fleet)

RTOL = 1e-9


def _reference(p, D, pens):
    """(carbon_reduction_pct, total_penalty_pct, preservation_violation)
    in float64 on the host."""
    D = np.asarray(D, np.float64)
    mci = np.asarray(p.mci, np.float64)
    usage = np.asarray(p.usage, np.float64)
    if mci.ndim == 2:
        wmci = mci[np.asarray(p.region)]
        base = (usage * wmci).sum()
        car = (D * wmci).sum()
    else:
        base = (usage.sum(0) * mci).sum()
        car = (D @ mci).sum()
    n_days = max(1, p.T // p.day_hours)
    hours = n_days * p.day_hours
    days = D[:, :hours].reshape(p.W, n_days, p.day_hours).sum(-1)
    isb = np.asarray(p.is_batch)
    viol = np.abs(days[isb]).max() if isb.any() else 0.0
    pen = np.asarray(pens, np.float64).sum()
    return (100 * car / base,
            100 * pen / np.asarray(p.entitlement, np.float64).sum(), viol)


def _check(res, p, D, pens):
    car, pen, viol = _reference(p, D, pens)
    assert res.carbon_reduction_pct == pytest.approx(car, rel=RTOL, abs=0)
    assert res.total_penalty_pct == pytest.approx(pen, rel=RTOL, abs=0)
    assert abs(res.preservation_violation - viol) \
        <= RTOL * np.abs(np.asarray(D, np.float64)).max()


@pytest.fixture(scope="module")
def one_region():
    return synthetic_fleet(300, seed=5)          # W = 300, T = 48


@pytest.fixture(scope="module")
def eight_regions():
    """8 regions of uneven sizes, rows not sorted by region, region 3
    without a row."""
    p = synthetic_regional_fleet(
        200, ("CA", "WA", "AZ", "CO", "TX", "IL", "NY", "FL"), seed=6)
    rng = np.random.default_rng(6)
    region = rng.choice([0, 1, 2, 4, 5, 6, 7], size=p.W,
                        p=[0.3, 0.05, 0.2, 0.1, 0.15, 0.15, 0.05])
    return dataclasses.replace(p, region=region.astype(np.int32))


def _plan(p, seed: int):
    """A float32 plan of mixed signs whose batch rows nearly preserve
    each day's total (the sums cancel), and float32 penalties."""
    rng = np.random.default_rng(seed)
    D = rng.uniform(-0.3, 0.4, p.usage.shape) * np.asarray(p.usage)
    n_days = max(1, p.T // p.day_hours)
    hours = n_days * p.day_hours
    days = D[:, :hours].reshape(p.W, n_days, p.day_hours)
    days -= np.asarray(p.is_batch)[:, None, None] \
        * days.mean(-1, keepdims=True)
    D[:, :hours] = days.reshape(p.W, hours)
    pens = rng.uniform(0.0, 2.0, p.W)
    return D.astype(np.float32), pens.astype(np.float32)


@pytest.mark.parametrize("fleet", ["one_region", "eight_regions"])
def test_held_plan_report_matches_float64(fleet, request):
    p = request.getfixturevalue(fleet)
    D, pens = _plan(p, 1)
    res = _report(p, D, pens, iters=0)
    _check(res, p, D, pens)
    np.testing.assert_array_equal(res.hour0, D[:, 0])
    # the day sums cancel: plain float32 sums read noise at this bound
    f32 = D[:, :48].reshape(p.W, 2, 24).sum(-1, dtype=np.float32)
    noise = np.abs(f32[np.asarray(p.is_batch)]).max()
    assert abs(noise - res.preservation_violation) \
        > RTOL * np.abs(D).max()


def test_report_of_a_window_that_is_not_whole_days(one_region):
    """T = 40: one whole day of 24 hours, the last 16 in no day."""
    p = dataclasses.replace(one_region, usage=one_region.usage[:, :40],
                            jobs=one_region.jobs[:, :40],
                            mci=np.asarray(one_region.mci)[:40])
    D, pens = _plan(p, 2)
    D[:, 24:] += 1.0           # hours past the last whole day count nowhere
    _check(_report(p, D, pens, iters=0), p, D, pens)


@pytest.mark.parametrize("fleet", ["one_region", "eight_regions"])
def test_solo_lane_reports_from_its_own_program(fleet, request):
    """The CR1 solo lane returns hour 0 and the sums from the solve's own
    program; the report they give matches float64 sums of the plan and
    penalties that program computed."""
    p = request.getfixturevalue(fleet)
    order, offsets = report_layout(p)
    out = api._TICK["cr1"].plain(_jit_view(p), CR1().lam,
                                 EngineState.cold(jnp.zeros(p.usage.shape)),
                                 steps=8, use_kernel=False, order=order,
                                 offsets=offsets)
    res = api._device_report(p, out, iters=8, tel=0)
    D, pens = jax.device_get(out[:2])
    _check(res, p, D, pens)
    np.testing.assert_array_equal(res.D, D)
    np.testing.assert_array_equal(res.hour0, D[:, 0])


def test_sweep_lane_reports_each_policy(one_region):
    p = one_region
    pols = [CR1(lam=1.0), CR1(lam=2.0)]
    res = sweep(p, pols, ctx=SolveContext(steps=8, use_kernel=False))
    init = EngineState(x=jnp.zeros((2,) + p.usage.shape),
                       lam_eq=jnp.zeros((2, 0)), lam_in=jnp.zeros((2, 0)),
                       mu=jnp.full((2,), CR1.mu0))
    Ds, pens, _ = jax.device_get(api._BATCH.plain(
        _jit_view(p), (), jnp.asarray([1.0, 2.0], jnp.float32), init, None,
        keys=(), mode="cr1", steps=8, outer=1, use_kernel=False))
    for r, D, pen in zip(res, Ds, pens):
        np.testing.assert_array_equal(r.D, D)
        _check(r, p, D, pen)


def test_host_computed_plan_reports_in_float64(eight_regions):
    """B1's plan is float64 on the host; it goes up as float32 pairs, so
    its report keeps float64 accuracy."""
    p = eight_regions
    res = B1(F=0.6).solve(p)
    assert res.D.dtype == np.float64
    pens = np.asarray(fleet_penalties(p, jnp.asarray(res.D), False))
    _check(res, p, res.D, pens)
