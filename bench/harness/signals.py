"""Marginal carbon intensity series and their revised forecasts.

The benchmark's own copy of the signal shapes the configurations state
(a duck curve: a midday solar trough and an evening ramp), so that the
traffic a cell receives does not change when the program's signal code
does. Every draw is tuple-seeded from the run's `--seed`.
"""
from __future__ import annotations

import numpy as np


def duck_curve(hours: int, peak: float, trough_frac: float, *,
               solar_center: float, solar_width: float,
               evening_bump: float, noise: float, seed) -> np.ndarray:
    """(hours,) kg CO2/MWh: solar depresses midday intensity, the evening
    ramp brings gas peakers to the margin, plus multiplicative noise."""
    rng = np.random.default_rng(seed)
    hour_of_day = np.arange(hours) % 24
    solar = np.exp(-0.5 * ((hour_of_day - solar_center) / solar_width) ** 2)
    evening = np.exp(-0.5 * ((hour_of_day - 19.0) / 2.0) ** 2)
    base = 1.0 - (1.0 - trough_frac) * solar + evening_bump * evening
    series = peak * base / base.max()
    series = series * (1.0 + noise * rng.standard_normal(hours))
    return np.clip(series, 0.0, None)


def actual_series(signal: dict, hours: int, seed: int) -> np.ndarray:
    """(R, hours) realized intensity per region, from a configuration's
    `signal` block. `regions` lists one entry per region: its trough
    fraction and its UTC offset (a local-time trace rolled onto the
    coordinator's UTC clock)."""
    shape = {k: signal[k] for k in ("solar_center", "solar_width",
                                    "evening_bump", "noise")}
    rows = []
    for r, reg in enumerate(signal["regions"]):
        s = duck_curve(hours, signal["peak"], reg["trough_frac"], **shape,
                       seed=(seed, 1, r))
        rows.append(np.roll(s, -int(reg.get("utc_offset", 0))))
    return np.stack(rows)


def forecasts(actual: np.ndarray, horizon: int, n_ticks: int,
              sigma: float, nowcast: float, seed: int) -> np.ndarray:
    """(n_ticks, R, horizon) revised forecasts: at tick t, hours
    [t, t+horizon) of `actual` with multiplicative error growing as
    `sigma * sqrt(lead + nowcast)` (persistence with lead-time noise)."""
    R, hours = actual.shape
    if hours < n_ticks + horizon - 1:
        raise ValueError(f"actual series of {hours} h cannot feed "
                         f"{n_ticks} ticks of a {horizon} h horizon")
    lead = np.sqrt(np.arange(horizon) + nowcast)
    out = np.empty((n_ticks, R, horizon))
    for t in range(n_ticks):
        rng = np.random.default_rng((seed, 2, t))
        err = sigma * lead * rng.standard_normal((R, horizon))
        out[t] = np.clip(actual[:, t:t + horizon] * (1.0 + err), 0.0, None)
    return out
