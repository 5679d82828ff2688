"""The general generator: a configuration and a traffic mix in, the
program's inputs out.

The fleet is built by the program's public fleet builder that the
configuration names (its inputs are the data, as weights are for a
model). The forecasts are the benchmark's own (`signals`), handed to the
program through `carbon.ForecastStream`'s replay mode, one stream per
region, and the ticks are issued through
`streaming.RollingHorizonSolver.step`.
"""
from __future__ import annotations

import importlib

import numpy as np

from harness import signals

FLEET_FIELDS = ("usage", "jobs", "entitlement", "k", "rts_coeffs", "betas",
                "x2_kind", "is_batch")


def build_fleet(config: dict, seed: int):
    """The configuration's fleet, from its named public builder."""
    f = config["fleet"]
    mod, fn = f["builder"].rsplit(".", 1)
    return getattr(importlib.import_module(mod), fn)(**f["args"], seed=seed)


def fleet_arrays(problem) -> dict:
    """The fleet's input arrays as numpy, with a region id per row (all
    zero for a single-region fleet)."""
    out = {k: np.asarray(getattr(problem, k)) for k in FLEET_FIELDS}
    region = getattr(problem, "region", None)
    out["region"] = (np.zeros(problem.W, np.int64) if region is None
                     else np.asarray(region, np.int64))
    return out


def make_forecasts(config: dict, traffic: dict, T: int, seed: int):
    """(actual (R, hours), forecasts (max_ticks, R, T)) from the seed."""
    n = int(traffic["max_ticks"])
    fc = traffic["forecast"]
    actual = signals.actual_series(config["signal"], n + T - 1, seed)
    return actual, signals.forecasts(actual, T, n, float(fc["sigma"]),
                                     float(fc["nowcast"]), seed)


def make_solver(problem, traffic: dict, actual: np.ndarray,
                forecasts: np.ndarray):
    """A `RollingHorizonSolver` over replayed forecasts, one stream per
    region, with the traffic's policy and solver settings."""
    from repro.core import api
    from repro.core.carbon import ForecastStream
    from repro.core.streaming import RollingHorizonSolver
    policy = getattr(api, traffic["policy"])(**traffic["policy_args"])
    T = forecasts.shape[2]
    streams = [ForecastStream(actual=actual[r], horizon=T,
                              replay=forecasts[:, r, :])
               for r in range(forecasts.shape[1])]
    return RollingHorizonSolver(problem, streams, policy=policy,
                                **traffic["solver"])


def sample_rows(W: int, n: int, seed: int) -> np.ndarray:
    """Sorted rows of the fleet that the correctness check compares."""
    rng = np.random.default_rng((seed, 3))
    return np.sort(rng.choice(W, size=min(n, W), replace=False))
