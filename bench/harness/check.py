"""The comparison that decides `correct`.

Every number compares what the timed path committed with what the
plain reference commits for the same sampled workloads and ticks. A
cell holds a chosen few of them to limits (`bench/limits/<cell>.json`):
those whose program readings lie well below the control's. The others
are printed as readings.

Per workload, per tick:
  commit_rel          relative Frobenius gap of the hour-0 commitments
Per workload, over the window:
  commit_mean_p50     median over workloads of the gap of each workload's
                      mean commitment, relative to the reference's mean
                      absolute commitment per workload
  commit_mean_rel     the same gaps summed (L1), relative to the
                      reference's sum
Per region (the whole fleet when R = 1):
  commit_total_rel    widest gap of a tick's committed total, relative to
                      the region's load in the committed hour
  energy_total_rel    widest gap of the window's committed energy,
                      relative to the region's load over the window
Carbon and penalty:
  committed_carbon_pp gap of the carbon the committed hours eliminate,
                      priced at each tick's hour-0 forecast, in points
                      of those hours' baseline carbon
  carbon_pp           gap of the last plan's carbon reduction, in points
                      of the sampled workloads' baseline
  penalty_pp          gap of the last plan's penalty, in points of the
                      sampled workloads' entitlement
  plan_rel            relative gap of the last tick's full plan
"""
from __future__ import annotations

import math

import numpy as np


def _rel(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.linalg.norm((a - b).ravel())
                 / max(np.linalg.norm(b.ravel()), 1e-30))


def numbers(got: dict, want: dict, replay, t_first: int,
            mci: np.ndarray) -> dict:
    """`got` and `want` each hold `commits` (n, S) for ticks t_first ..
    t_first + n - 1 and `plan` (S, T) of the last of them, for the same
    sampled workloads; `replay` gives their inputs and evaluates
    penalties; `mci` is those ticks' forecasts (n, R, T)."""
    cg, cw = got["commits"], want["commits"]
    n = len(cg)
    t_last = t_first + n - 1
    region = replay.region
    usage = np.asarray(replay.rows["usage"], np.float64)
    T = usage.shape[1]
    load = usage[:, np.arange(t_first, t_last + 1) % T].T    # (n, S)
    price = mci[:, :, 0][:, region]                           # (n, S)
    per_tick = per_window = 0.0
    for r in np.unique(region):
        sel = region == r
        gap = (cg[:, sel] - cw[:, sel]).sum(1)
        per_tick = max(per_tick, float(np.abs(gap / load[:, sel].sum(1))
                                       .max()))
        per_window = max(per_window, abs(float(gap.sum()))
                         / float(load[:, sel].sum()))
    mean_gap = np.abs(cg.mean(0) - cw.mean(0))
    mean_ref = max(float(np.abs(cw.mean(0)).mean()), 1e-30)
    wmci = mci[-1][region]
    base = float((np.roll(usage, -t_last, axis=1) * wmci).sum())
    return {
        "commit_rel": _rel(cg, cw),
        "commit_mean_p50": float(np.median(mean_gap) / mean_ref),
        "commit_mean_rel": float(mean_gap.sum()) / (mean_ref * len(mean_gap)),
        "commit_total_rel": per_tick,
        "energy_total_rel": per_window,
        "committed_carbon_pp": 100.0 * abs(float(((cg - cw) * price).sum()))
        / float((load * price).sum()),
        "carbon_pp": 100.0 * abs(float(((got["plan"] - want["plan"])
                                        * wmci).sum())) / base,
        "penalty_pp": abs(replay.penalty_pct(got["plan"], t_last)
                          - replay.penalty_pct(want["plan"], t_last)),
        "plan_rel": _rel(got["plan"], want["plan"]),
    }


def verdict(nums: dict, limits: dict) -> bool:
    """True when every limited number is finite and within its limit."""
    missing = set(limits) - set(nums)
    if missing:
        raise KeyError(f"limits name numbers never computed: {missing}")
    return all(math.isfinite(nums[k]) and nums[k] <= lim
               for k, lim in limits.items())
