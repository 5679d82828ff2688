"""jit'd wrapper for the fleet DR feature kernel (backend-aware dispatch).

The wrapper carries an analytic custom VJP: the solver hot loop
differentiates penalties through these features every Adam step, and
`pallas_call` has no registered transpose. The backward pass is closed
form — each feature is Σ_t max(cumsum(r), 0) for a per-hour rate r, so
∂/∂d is a reversed cumulative sum of the active-hinge indicator (with
the rounding floor of `features.backlog_pos`) times ∂r/∂d. Gradients
flow to `d` only (usage/jobs are problem constants in every solver
path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.features import backlog_active
from repro.kernels.al_step.ref import prefix_sum
from repro.kernels.dispatch import interpret_default
from repro.kernels.dr_features.kernel import dr_features_pallas


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dr_features(interpret: bool, d, usage, jobs):
    return dr_features_pallas(d, usage, jobs, interpret=interpret)


def _fwd(interpret, d, usage, jobs):
    return _dr_features(interpret, d, usage, jobs), (d, usage, jobs)


def _revcum_active(rate):
    """Reversed running sum of the active-hinge indicator of rate's
    backlog — ∂/∂rate of Σ_t backlog_pos(cumsum(rate))_t."""
    active = backlog_active(prefix_sum(rate), rate).astype(rate.dtype)
    return prefix_sum(active, reverse=True)


def _bwd(interpret, res, ct):
    d, usage, jobs = res
    ju = jobs / usage
    d_ct = (ct[:, 0:1] * ju * _revcum_active(ju * d)             # wait_jobs
            + ct[:, 1:2] * _revcum_active(d)                    # wait_power
            + ct[:, 2:3] * 2.0 * ju * jnp.abs(d)
            * _revcum_active(ju * d * jnp.abs(d))               # wait_sq
            + ct[:, 3:4] * ju * (d > 0).astype(d.dtype))
    return d_ct, jnp.zeros_like(usage), jnp.zeros_like(jobs)


_dr_features.defvjp(_fwd, _bwd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _dr_features_jit(d, usage, jobs, interpret: bool):
    return _dr_features(interpret, d, usage, jobs)


def dr_features(d, usage, jobs, interpret: bool | None = None):
    """(W, T) fleet adjustment/usage/job matrices -> (W, 4) features.

    `interpret=None` auto-selects: compiled kernel on TPU, interpret
    fallback on CPU (override with REPRO_PALLAS_INTERPRET). Resolved
    *outside* the jit boundary so a changed env override is not masked by
    a stale trace cached under the `None` key.
    """
    return _dr_features_jit(d, usage, jobs, interpret_default(interpret))
