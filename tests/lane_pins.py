"""Pinned results of every CR1/CR2 engine lane, through the public entry
points only, so the same cases run on any version of the lanes.

`cases()` names each (policy, lane, regions) case; `run_case(name)`
solves it on the CPU and returns its arrays: the plan (or plans), the
hour-0 commitments and the report's numbers. `tests/test_lane_pins.py`
compares them with `tests/lane_pins.npz`, which this script writes:

    PYTHONPATH=src JAX_PLATFORMS=cpu python tests/lane_pins.py

Fleets are small (W = 16, T = 48) and step budgets short: the pins hold
the lanes' arithmetic, not converged optima."""
from __future__ import annotations

import itertools
import os
import sys

import numpy as np

POLICIES = ("cr1", "cr2")
LANES = ("solo", "donated", "sanitized", "sweep", "day", "ensemble")
REGIONS = (1, 3)
PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "lane_pins.npz")
W = 16
STEPS = 30


def cases() -> list[str]:
    return [f"{pol}-{lane}-r{r}" for pol, lane, r
            in itertools.product(POLICIES, LANES, REGIONS)]


def _fleet(regions: int):
    from repro.core.fleet_solver import (synthetic_fleet,
                                         synthetic_regional_fleet)
    if regions == 1:
        return synthetic_fleet(W, seed=0)
    return synthetic_regional_fleet(W, ["CA", "TX", "NY"][:regions],
                                    seed=0)


def _policy(name: str, knob: int = 0):
    from repro.core.api import CR1, CR2
    if name == "cr1":
        return CR1(lam=(1.45, 2.2)[knob])
    return CR2(cap_frac=(0.78, 0.74)[knob], outer=2)


def _numbers(res) -> np.ndarray:
    return np.asarray([res.carbon_reduction_pct, res.total_penalty_pct,
                       res.preservation_violation, res.carbon_base])


def _result(res) -> dict:
    return {"D": np.asarray(res.D), "hour0": np.asarray(res.hour0),
            "numbers": _numbers(res)}


def _stack_results(results) -> dict:
    return {k: np.stack([_result(r)[k] for r in results])
            for k in ("D", "hour0", "numbers")}


def run_case(case: str) -> dict:
    """The arrays of one case, keyed "D", "hour0" and "numbers"."""
    from repro.core.api import SolveContext, ensemble, solve, solve_day, sweep
    from repro.core.scenario import (DuckPerturb, FleetJitter,
                                     RegionalDivergence, resolve_scenarios)
    name, lane, r = case.split("-")
    p = _fleet(int(r[1:]))
    pol = _policy(name)
    ctx = SolveContext(steps=STEPS, use_kernel=False)
    if lane == "solo":
        return _result(solve(p, pol, ctx=ctx))
    if lane == "donated":
        cold = solve(p, pol, ctx=ctx)
        return _result(solve(p, pol, ctx=SolveContext(
            steps=STEPS // 2, use_kernel=False, warm=cold.state,
            donate=True, shift=1, reset_mu=True)))
    if lane == "sanitized":
        return _result(solve(p, pol, ctx=SolveContext(
            steps=STEPS, use_kernel=False, sanitize=True)))
    if lane == "sweep":
        return _stack_results(sweep(p, [pol, _policy(name, 1)], ctx=ctx))
    if lane == "day":
        mci = np.asarray(p.mci, np.float32)
        stack = np.stack([mci * (1.0 + 0.02 * t) for t in range(3)])
        day = solve_day(p, pol, stack, ctx=ctx, cold_steps=STEPS,
                        warm_steps=STEPS // 3)
        out = _result(day.last)
        out["hour0"] = np.asarray(day.committed)
        return out
    if lane == "ensemble":
        mci = RegionalDivergence if p.is_multiregion else DuckPerturb
        stack = resolve_scenarios([mci(n_scenarios=2, seed=1),
                                   FleetJitter(n_scenarios=1, seed=2)], p)
        res = ensemble(p, pol, stack, ctx=ctx)
        assert res.batched
        return {"D": np.asarray(res.D), "hour0": np.asarray(res.D)[:, :, 0],
                "numbers": np.stack([res.carbon_reduction_pct,
                                     res.total_penalty_pct,
                                     res.preservation_violation], axis=1)}
    raise ValueError(case)


def main() -> None:
    out = {}
    for case in cases():
        for k, v in run_case(case).items():
            out[f"{case}/{k}"] = v
        print(case, flush=True)
    np.savez_compressed(sys.argv[1] if len(sys.argv) > 1 else PATH, **out)


if __name__ == "__main__":
    main()
