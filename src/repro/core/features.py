"""Engineered penalty-model features (paper Table IV), in JAX.

All features are functions of the hourly adjustment vector d (positive =
curtail) and are built from positive-part cumulative sums — the queue
integral of deferred work. They are differentiable almost everywhere (relu
compositions), which is what lets the fleet solver optimize through them;
a softplus-smoothed variant is provided for solvers that prefer C¹.

Shapes: d, usage, jobs are (T,) for one workload or (W, T) batched; every
function maps to a scalar per workload ((,) or (W,)).

The Pallas kernel `repro.kernels.dr_features` computes the same quantities
for large fleets; `repro.kernels.dr_features.ref` must match this module
(it is the oracle used in kernel tests).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

Array = jax.Array


#: A running backlog within this fraction of its row's total |rate| mass
#: counts as empty. A day-preserving plan drains the queue to exactly zero
#: at every day end, so the running sum there is pure rounding noise; left
#: to its sign, the hinge's subgradient would hang on summation order (XLA
#: fusion, kernel vs jnp, device count) and the solver's path with it.
QUEUE_RTOL = 1e-5


def _pos(x: Array, smooth: float = 0.0) -> Array:
    """Positive part; softplus-smoothed when smooth > 0."""
    if smooth > 0.0:
        return smooth * jax.nn.softplus(x / smooth)
    return jnp.maximum(x, 0.0)


def backlog_active(cum: Array, rate: Array, axis: int = -1) -> Array:
    """Whether a running backlog `cum` (a prefix sum of `rate` along
    `axis`, the time axis) is positive beyond rounding:
    `cum > QUEUE_RTOL·Σ|rate|`. This is the hinge subgradient the kernels
    use."""
    return cum > QUEUE_RTOL * jnp.abs(rate).sum(axis=axis, keepdims=True)


def backlog_pos(cum: Array, rate: Array) -> Array:
    """Positive part of a running backlog, read as zero within rounding
    of zero; its autodiff is `backlog_active`."""
    return jnp.where(backlog_active(cum, rate), cum, 0.0)


def _queue(rate: Array, smooth: float, lag: int = 0) -> Array:
    """Σ_t (Σ_{t'<=t-lag} rate_{t'})⁺ over the trailing time axis."""
    cum = jnp.cumsum(rate, axis=-1)[..., : rate.shape[-1] - lag]
    if smooth > 0.0:
        return _pos(cum, smooth).sum(axis=-1)
    return backlog_pos(cum, rate).sum(axis=-1)


def waiting_time_jobs(d: Array, usage: Array, jobs: Array,
                      smooth: float = 0.0) -> Array:
    """Σ_t ( Σ_{t'<=t} J_{t'} · d_{t'}/U_{t'} )⁺   [job·hour]."""
    return _queue(jobs * d / usage, smooth)


def waiting_time_power(d: Array, smooth: float = 0.0) -> Array:
    """Σ_t ( Σ_{t'<=t} d_{t'} )⁺   [NP·hour] — selected as x1 for both
    AI training and data pipeline."""
    return _queue(d, smooth)


def waiting_time_squared(d: Array, usage: Array, jobs: Array,
                         smooth: float = 0.0) -> Array:
    """Σ_t ( Σ_{t'<=t} J_{t'} · d_{t'}²/U_{t'} )⁺ — convexity feature,
    selected as x2 for data pipeline.

    Note: the summand uses signed d·|d| rather than d² so that boosts
    (d<0) relieve the queue integral, matching the cumulative-backlog
    semantics of the other features (a pure square would make boosting
    *increase* the penalty, which the paper's fitted model does not do).
    """
    return _queue(jobs * d * jnp.abs(d) / usage, smooth)


def num_jobs_delayed(d: Array, usage: Array, jobs: Array,
                     smooth: float = 0.0) -> Array:
    """Σ_{t'} J_{t'} · d_{t'}⁺ / U_{t'} — non-cumulative count of affected
    jobs, selected as x2 for AI training."""
    return (jobs * _pos(d, smooth) / usage).sum(axis=-1)


def total_tardiness(d: Array, usage: Array, jobs: Array, slo_hours: int,
                    smooth: float = 0.0) -> Array:
    """Σ_t ( Σ_{t'<=t-SLO} J_{t'} · d_{t'}/U_{t'} )⁺ — overdue queue hours.

    The inner sum lags the outer index by `slo_hours`: work deferred at t'
    only becomes tardy once it has waited SLO hours.
    """
    rate = jobs * d / usage
    if slo_hours >= rate.shape[-1]:
        return jnp.zeros(rate.shape[:-1], rate.dtype)
    return _queue(rate, smooth, lag=slo_hours)


FEATURE_NAMES = (
    "waiting_time_jobs",
    "waiting_time_power",
    "waiting_time_squared",
    "num_jobs_delayed",
    "total_tardiness",
)


def feature_matrix(d: Array, usage: Array, jobs: Array, slo_hours: int = 4,
                   smooth: float = 0.0, include_tardiness: bool = True,
                   ) -> Array:
    """Stack Table-IV features -> (..., F). F = 5 with tardiness, else 4
    (tardiness is N/A for no-SLO workloads — Table IV)."""
    feats = [
        waiting_time_jobs(d, usage, jobs, smooth),
        waiting_time_power(d, smooth),
        waiting_time_squared(d, usage, jobs, smooth),
        num_jobs_delayed(d, usage, jobs, smooth),
    ]
    if include_tardiness:
        feats.append(total_tardiness(d, usage, jobs, slo_hours, smooth))
    return jnp.stack(feats, axis=-1)


# Selections published in Table IV.
SELECTED = {
    # x1, x2 for each batch workload family.
    "AITraining": ("waiting_time_power", "num_jobs_delayed"),
    "DataPipeline": ("waiting_time_power", "waiting_time_squared"),
}


def selected_features(workload: str, d: Array, usage: Array, jobs: Array,
                      slo_hours: int = 4, smooth: float = 0.0) -> Array:
    """(x1, x2) per Table IV's published selection -> (..., 2)."""
    fns: dict[str, Callable[..., Array]] = {
        "waiting_time_jobs": lambda: waiting_time_jobs(d, usage, jobs, smooth),
        "waiting_time_power": lambda: waiting_time_power(d, smooth),
        "waiting_time_squared": lambda: waiting_time_squared(d, usage, jobs, smooth),
        "num_jobs_delayed": lambda: num_jobs_delayed(d, usage, jobs, smooth),
        "total_tardiness": lambda: total_tardiness(d, usage, jobs, slo_hours, smooth),
    }
    names = SELECTED[workload]
    return jnp.stack([fns[n]() for n in names], axis=-1)
