"""Pure-jnp oracle for the fused AL inner-step kernel.

Mirrors `kernel.py` op-for-op on (W, T) arrays: the analytic augmented-
Lagrangian gradient (CR1's fixed-weight penalty or CR2's equality-
multiplier form), a bias-corrected Adam update, and the box +
day-mean-preserving projection — `k_steps` of them per call, carrying
(x, m, v) exactly like one kernel invocation does.

The gradient/projection math is *shared* with the kernel body (it
imports `_pen_and_grad` / `_project` from here) — deliberately: the
analytic subgradient is discontinuous at hinge boundaries, so a 1-ulp
difference between two formulations (e.g. reshape-mean vs matmul-mean
day averaging) can flip an active-hinge indicator after a few steps and
blow a bitwise-tight parity budget on nothing. Kernel-vs-ref therefore
checks the *tiling/padding/memory movement* (what Pallas adds), while
the semantic check against an independent implementation — autodiff of
`fleet_penalties` through the generic engine inner loop — lives in the
fused-vs-generic solve-level tests with an appropriately loose
tolerance.

Gradient convention: hinge boundaries use the strict `>` subgradient
(zero at the tie), matching the analytic custom VJP in
`kernels/dr_features/ops.py`. The queue-integral hinges read a backlog
within rounding of zero as empty (`features.backlog_pos`, whose
autodiff gives the same indicator); the `max(z, 0)` and `max(x, 0)`
hinges differ from jnp autodiff of `max` (0.5 at exact ties) only on
measure-zero inputs like the all-zeros cold start.

Row-parameter packing (see `ops.pack_rows`) — `rowp` is (W, 12) f32:

  col 0-2   rts_coeffs (a3, a2, a1)
  col 3-5   betas (b0, b1, b2)
  col 6     k (annual job volume scale)
  col 7     x2_kind (>0.5: wait_sq, else njobs_delayed)
  col 8     is_batch (>0.5: batch penalty + day-mean projection)
  col 9     refs (CR2 per-workload penalty reference; 0 for CR1)
  col 10    lam_eq (CR2 equality multiplier, refreshed per outer round)
  col 11    step multiplier (per-row learning-rate scale; all-ones when
            the step scale is the fleet-global scalar folded into
            `lr_scale` — x·1.0 is exact, so the scalar path is bitwise
            the pre-col-11 kernel)

Scalar packing — `scal` is (1, 8) f32:

  [coef0, mu, inv_scale, lr_scale, t0, 0, 0, 0]

where `coef0 = lam * pen_norm` (CR1 penalty weight; unused for CR2),
`inv_scale = 1/scale` (CR2 residual normalizer; unused for CR1),
`lr_scale = cfg.lr * step_scale`, and `t0` is the Adam step count already
taken this outer round (bias correction resumes at t0 + 1).

`cvec` is (1, T) — or (W, T) for per-row carbon weights (multi-region
fleets, where each row prices carbon on its region's normalizer and
trace). Multi-region per-ROW penalty weights reach the same scalar slots
by folding: CR1 folds `lam·pen_w` into col-6 `k` (the gradient is linear
in k) with `coef0 = 1`; CR2 folds `1/scale_w` into `k` and `refs` with
`inv_scale = 1` (h and coef·dpen are unchanged algebraically — see
`api._al_fused_inner`). The kernel itself stays region-blind.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.features import backlog_active


def _tri_mask(T, reverse):
    """Static (T, T) triangular mask: m[s, t] = 1 iff s <= t (s >= t
    when `reverse`), built with `broadcasted_iota` like `_day_mask`."""
    s = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    t = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    keep = s >= t if reverse else s <= t
    return jnp.where(keep, jnp.float32(1.0), jnp.float32(0.0))


def prefix_sum(x, reverse: bool = False):
    """Running sum of a (W, T) f32 array along T, from the left (from
    the right when `reverse`), as one matmul against a triangular mask:
    Pallas TPU has no cumsum lowering, and T <= 48 makes the T×T mask
    tiny. HIGHEST precision keeps the MXU at full f32."""
    return jax.lax.dot_general(
        x, _tri_mask(x.shape[1], reverse), (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _pen_and_grad(x, inv_u, ju, rowp):
    """Fleet penalty vector (W, 1) and its analytic gradient (W, T).

    Same math as `fleet_solver.fleet_penalties` + the dr_features custom
    VJP, fused: RTS rows get the cubic smooth penalty, batch rows the
    hinged linear model over the queue-integral features.
    """
    a3, a2, a1 = rowp[:, 0:1], rowp[:, 1:2], rowp[:, 2:3]
    b0, b1, b2 = rowp[:, 3:4], rowp[:, 4:5], rowp[:, 5:6]
    kk, x2k, isb = rowp[:, 6:7], rowp[:, 7:8], rowp[:, 8:9]

    # RTS: pen = k·Σ_t a3·δ³ + a2·δ² + a1·δ, δ = d/usage.
    delta = x * inv_u
    rts_pen = kk * (a3 * delta ** 3 + a2 * delta ** 2
                    + a1 * delta).sum(axis=1, keepdims=True)
    rts_g = kk * (3.0 * a3 * delta ** 2 + 2.0 * a2 * delta + a1) * inv_u

    # Batch: pen = k·max(b0 + b1·wait_power + b2·x2, 0).
    r2 = ju * x * jnp.abs(x)
    c1, c2 = prefix_sum(x), prefix_sum(r2)
    a1, a2 = backlog_active(c1, x), backlog_active(c2, r2)
    x1 = jnp.where(a1, c1, 0.0).sum(axis=1, keepdims=True)
    x2s = jnp.where(a2, c2, 0.0).sum(axis=1, keepdims=True)
    nj = (ju * jnp.maximum(x, 0.0)).sum(axis=1, keepdims=True)
    x2 = jnp.where(x2k > 0.5, x2s, nj)
    z = b0 + b1 * x1 + b2 * x2
    batch_pen = kk * jnp.maximum(z, 0.0)

    g1 = prefix_sum(a1.astype(x.dtype), reverse=True)
    g2s = 2.0 * ju * jnp.abs(x) * prefix_sum(a2.astype(x.dtype),
                                             reverse=True)
    g2n = ju * (x > 0).astype(x.dtype)
    gx2 = jnp.where(x2k > 0.5, g2s, g2n)
    batch_g = kk * (z > 0).astype(x.dtype) * (b1 * g1 + b2 * gx2)

    pen = jnp.where(isb > 0.5, batch_pen, rts_pen)
    dpen = jnp.where(isb > 0.5, batch_g, rts_g)
    return pen, dpen


def _day_mask(T, day_hours):
    """Static (n_days, T) day-membership mask: mask[d, t] = 1 iff hour t
    belongs to day d (hours past the last whole day belong to none).
    Built with `broadcasted_iota` so the same code runs inside a Pallas
    kernel body (no reshapes, which the TPU vector layout dislikes)."""
    n_days = max(1, T // day_hours)
    span = n_days * day_hours
    drow = jax.lax.broadcasted_iota(jnp.int32, (n_days, T), 0)
    tcol = jax.lax.broadcasted_iota(jnp.int32, (n_days, T), 1)
    return jnp.where((tcol // day_hours == drow) & (tcol < span),
                     jnp.float32(1.0), jnp.float32(0.0))


def _project(x, lo, hi, isb, day_hours):
    """Box clip + 3 rounds of day-mean removal for batch rows — the same
    fixed-point iteration as `fleet_solver._projection`, with day means
    expressed as two matmuls against the static day mask instead of a
    reshape (TPU-layout-friendly; shared by the kernel body)."""
    f32 = jnp.float32
    mask = _day_mask(x.shape[1], day_hours)
    batch_rows = isb > 0.5
    x = jnp.clip(x, lo, hi)
    for _ in range(3):
        mean = jax.lax.dot_general(
            x, mask, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=f32) * (1.0 / day_hours)
        sub = jax.lax.dot_general(
            mean, mask, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=f32)
        x = jnp.clip(jnp.where(batch_rows, x - sub, x), lo, hi)
    return x


def al_step_ref(x, m, v, usage, jobs, lo, hi, rowp, cvec, scal, *,
                mode: str, k_steps: int, beta1: float = 0.9,
                beta2: float = 0.999, eps: float = 1e-8,
                day_hours: int = 24):
    """Run `k_steps` fused projected-Adam AL steps; returns (x, m, v).

    x: (W, T) f32 primal iterate; m/v: (W, T) Adam moments (any float
    dtype — up-cast to f32 for arithmetic, stored back in their dtype);
    cvec: (1, T) carbon gradient term (−car_norm·mci), or (W, T) for
    per-row carbon weights; rowp/scal: packed parameters, see module
    docstring.
    """
    if mode not in ("cr1", "cr2"):
        raise ValueError(f"mode must be cr1|cr2, got {mode!r}")
    f32 = jnp.float32
    x = x.astype(f32)
    mdt = m.dtype
    m = m.astype(f32)
    v = v.astype(f32)
    inv_u = 1.0 / usage.astype(f32)
    ju = jobs.astype(f32) * inv_u
    isb = rowp[:, 8:9]
    refs, lam_eq, stepw = rowp[:, 9:10], rowp[:, 10:11], rowp[:, 11:12]
    coef0, mu = scal[0, 0], scal[0, 1]
    inv_scale, lr_scale, t0 = scal[0, 2], scal[0, 3], scal[0, 4]
    lb1, lb2 = jnp.log(f32(beta1)), jnp.log(f32(beta2))

    for i in range(k_steps):
        pen, dpen = _pen_and_grad(x, inv_u, ju, rowp)
        if mode == "cr1":
            coef = coef0
        else:
            # L = obj + lam_eq·h + (mu/2)·h², h = (pen − refs)/scale
            # ⇒ ∂L/∂pen = (lam_eq + mu·h)/scale.
            h = (pen - refs) * inv_scale
            coef = (lam_eq + mu * h) * inv_scale
        g = coef * dpen + cvec
        t = t0 + f32(i + 1)
        m = beta1 * m + (1.0 - beta1) * g
        v = beta2 * v + (1.0 - beta2) * g * g
        mhat = m / (1.0 - jnp.exp(t * lb1))
        vhat = v / (1.0 - jnp.exp(t * lb2))
        x = _project(x - lr_scale * stepw * mhat / (jnp.sqrt(vhat) + eps),
                     lo, hi, isb, day_hours)
    return x, m.astype(mdt), v.astype(mdt)
