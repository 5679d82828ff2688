"""Pure-jnp oracle for the fused AL inner-step kernel, and the math the
kernel body runs.

Layout: time-major. Every per-hour array is (T, W): hours down the
sublanes, workloads across the 128 lanes, so a TPU tile holds 128
workloads at full lane occupancy and every sum over hours is a sum down
the sublanes — exact f32 vector adds (a log-step scan within each
8-row vreg plus a carry between vregs for the running sums, plain
sublane sums for the row totals and day means), no matmul. `al_step_tm`
runs `k_steps` projected-Adam AL steps on such arrays: the analytic
augmented-Lagrangian gradient (CR1's fixed-weight penalty or CR2's
equality-multiplier form), a bias-corrected Adam update, and the box +
day-mean-preserving projection, carrying (x, m, v) exactly like one
kernel invocation does.

The kernel body (`kernel.py`) runs `al_step_tm` itself on each
(T, block_w) block — deliberately: the analytic subgradient is
discontinuous at hinge boundaries, so a 1-ulp difference between two
formulations (e.g. a different summation order in the day means) can
flip an active-hinge indicator after a few steps and blow a
bitwise-tight parity budget on nothing. Kernel-vs-ref therefore checks
the *tiling/padding/memory movement* (what Pallas adds), while the
semantic check against an independent implementation — autodiff of
`fleet_penalties` through the generic engine inner loop — lives in the
fused-vs-generic solve-level tests with an appropriately loose
tolerance. `al_step_ref` keeps the (W, T) interface of the rest of the
system and transposes at entry and exit.

Gradient convention: hinge boundaries use the strict `>` subgradient
(zero at the tie), matching the analytic custom VJP in
`kernels/dr_features/ops.py`. The queue-integral hinges read a backlog
within rounding of zero as empty (`features.backlog_pos`, whose
autodiff gives the same indicator); the `max(z, 0)` and `max(x, 0)`
hinges differ from jnp autodiff of `max` (0.5 at exact ties) only on
measure-zero inputs like the all-zeros cold start.

Row-parameter packing (see `ops.pack_rows`) — `rowp` is (12, W) f32
time-major (`al_step_ref` takes its transpose, (W, 12)); row r is:

  row 0-2   rts_coeffs (a3, a2, a1)
  row 3-5   betas (b0, b1, b2)
  row 6     k (annual job volume scale)
  row 7     x2_kind (>0.5: wait_sq, else njobs_delayed)
  row 8     is_batch (>0.5: batch penalty + day-mean projection)
  row 9     refs (CR2 per-workload penalty reference; 0 for CR1)
  row 10    lam_eq (CR2 equality multiplier, refreshed per outer round)
  row 11    step multiplier (per-workload learning-rate scale; all-ones
            when the step scale is the fleet-global scalar folded into
            `lr_scale` — x·1.0 is exact)

Scalar packing — `scal` is (1, 8) f32:

  [coef0, mu, inv_scale, lr_scale, t0, 0, 0, 0]

where `coef0 = lam * pen_norm` (CR1 penalty weight; unused for CR2),
`inv_scale = 1/scale` (CR2 residual normalizer; unused for CR1),
`lr_scale = cfg.lr * step_scale`, and `t0` is the Adam step count already
taken this outer round (bias correction resumes at t0 + 1).

`cvec` is (T, 1) — or (T, W) for per-workload carbon weights
(multi-region fleets, where each workload prices carbon on its region's
normalizer and trace). Multi-region per-workload penalty weights reach
the same scalar slots by folding: CR1 folds `lam·pen_w` into row-6 `k`
(the gradient is linear in k) with `coef0 = 1`; CR2 folds `1/scale_w`
into `k` and `refs` with `inv_scale = 1` (h and coef·dpen are unchanged
algebraically — see `api._al_fused_inner`). The kernel itself stays
region-blind.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.features import backlog_active

#: Rows of one f32 vreg: the running sums scan within groups this tall.
SUBLANES = 8


def _tri_mask(T, reverse):
    """Static (T, T) triangular mask: m[s, t] = 1 iff s <= t (s >= t
    when `reverse`), built with `broadcasted_iota`."""
    s = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    t = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    keep = s >= t if reverse else s <= t
    return jnp.where(keep, jnp.float32(1.0), jnp.float32(0.0))


def prefix_sum(x, reverse: bool = False):
    """Running sum of a (W, T) f32 array along T, from the left (from
    the right when `reverse`), as one matmul against a triangular mask:
    the `dr_features` kernel keeps hours on the lanes, where Pallas TPU
    has no cumsum lowering. HIGHEST precision keeps the MXU at full
    f32."""
    return jax.lax.dot_general(
        x, _tri_mask(x.shape[1], reverse), (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def running_sum(x, reverse: bool = False):
    """Running sum of a time-major (T, W) f32 array down its rows, from
    the first hour (from the last when `reverse`).

    Within each group of `SUBLANES` rows (one vreg tall) a log-step scan:
    roll the group down (up) by 1, 2 and 4 rows and add where the
    source row lies in the group. Then each group adds the total of the
    group before (after) it, which already holds its own carry. Only
    sublane shifts and vector adds, which lower in a Pallas TPU kernel
    for a T that is a multiple of 8 or not (a short last group).
    `jnp.roll` rather than `pltpu.roll` keeps the oracle
    runnable outside `jit` and under `vmap`; one kernel call at
    W = 100k, timed on the host clock on a v5e, takes 2.08 ms with it
    against 2.04."""
    T = x.shape[0]
    groups = []
    for lo in range(0, T, SUBLANES):
        g = x[lo:lo + SUBLANES]
        n = g.shape[0]
        row = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
        s = 1
        while s < n:
            if reverse:   # row r takes row r + s
                g = g + jnp.where(row < n - s, jnp.roll(g, n - s, 0), 0.0)
            else:         # row r takes row r - s
                g = g + jnp.where(row >= s, jnp.roll(g, s, 0), 0.0)
            s *= 2
        groups.append(g)
    if reverse:
        for i in range(len(groups) - 2, -1, -1):
            groups[i] = groups[i] + groups[i + 1][:1]
    else:
        for i in range(1, len(groups)):
            groups[i] = groups[i] + groups[i - 1][-1:]
    return jnp.concatenate(groups, axis=0) if len(groups) > 1 else groups[0]


def _pen_and_grad(x, inv_u, ju, rowp):
    """Fleet penalty row (1, W) and its analytic gradient (T, W), time
    major.

    Same math as `fleet_solver.fleet_penalties` + the dr_features custom
    VJP, fused: RTS workloads get the cubic smooth penalty, batch
    workloads the hinged linear model over the queue-integral features.
    """
    a3, a2, a1 = rowp[0:1], rowp[1:2], rowp[2:3]
    b0, b1, b2 = rowp[3:4], rowp[4:5], rowp[5:6]
    kk, x2k, isb = rowp[6:7], rowp[7:8], rowp[8:9]

    # RTS: pen = k·Σ_t a3·δ³ + a2·δ² + a1·δ, δ = d/usage.
    delta = x * inv_u
    rts_pen = kk * (a3 * delta ** 3 + a2 * delta ** 2
                    + a1 * delta).sum(axis=0, keepdims=True)
    rts_g = kk * (3.0 * a3 * delta ** 2 + 2.0 * a2 * delta + a1) * inv_u

    # Batch: pen = k·max(b0 + b1·wait_power + b2·x2, 0).
    r2 = ju * x * jnp.abs(x)
    c1, c2 = running_sum(x), running_sum(r2)
    a1, a2 = backlog_active(c1, x, axis=0), backlog_active(c2, r2, axis=0)
    x1 = jnp.where(a1, c1, 0.0).sum(axis=0, keepdims=True)
    x2s = jnp.where(a2, c2, 0.0).sum(axis=0, keepdims=True)
    nj = (ju * jnp.maximum(x, 0.0)).sum(axis=0, keepdims=True)
    x2 = jnp.where(x2k > 0.5, x2s, nj)
    z = b0 + b1 * x1 + b2 * x2
    batch_pen = kk * jnp.maximum(z, 0.0)

    g1 = running_sum(a1.astype(x.dtype), reverse=True)
    g2s = 2.0 * ju * jnp.abs(x) * running_sum(a2.astype(x.dtype),
                                              reverse=True)
    g2n = ju * (x > 0).astype(x.dtype)
    gx2 = jnp.where(x2k > 0.5, g2s, g2n)
    batch_g = kk * (z > 0).astype(x.dtype) * (b1 * g1 + b2 * gx2)

    pen = jnp.where(isb > 0.5, batch_pen, rts_pen)
    dpen = jnp.where(isb > 0.5, batch_g, rts_g)
    return pen, dpen


def _project(x, lo, hi, isb, day_hours):
    """Box clip + 3 rounds of day-mean removal for batch workloads on a
    time-major (T, W) array — the same fixed-point iteration as
    `fleet_solver._projection`. Each day's mean is a sum down its rows,
    broadcast back; hours past the last whole day belong to no day (a
    horizon shorter than a day is one day, still divided by
    `day_hours`)."""
    T = x.shape[0]
    n_days = max(1, T // day_hours)
    span = n_days * day_hours
    batch = isb > 0.5
    x = jnp.clip(x, lo, hi)
    for _ in range(3):
        parts = []
        for d in range(n_days):
            day = x[d * day_hours:(d + 1) * day_hours]
            mean = day.sum(axis=0, keepdims=True) * (1.0 / day_hours)
            parts.append(jnp.where(batch, day - mean, day))
        if span < T:
            parts.append(x[span:])
        x = jnp.clip(jnp.concatenate(parts, axis=0) if len(parts) > 1
                     else parts[0], lo, hi)
    return x


def al_step_tm(x, m, v, usage, jobs, lo, hi, rowp, cvec, scal, *,
               mode: str, k_steps: int, beta1: float = 0.9,
               beta2: float = 0.999, eps: float = 1e-8,
               day_hours: int = 24):
    """Run `k_steps` fused projected-Adam AL steps on time-major arrays;
    returns (x, m, v).

    x/usage/jobs/lo/hi: (T, W); m/v: (T, W) Adam moments (any float
    dtype — up-cast to f32 for arithmetic, returned in their dtype);
    rowp: (12, W); cvec: (T, 1) or (T, W); scal: (1, 8) — see the module
    docstring. The kernel body runs this on each block.
    """
    if mode not in ("cr1", "cr2"):
        raise ValueError(f"mode must be cr1|cr2, got {mode!r}")
    f32 = jnp.float32
    mdt, vdt = m.dtype, v.dtype
    x = x.astype(f32)
    m = m.astype(f32)
    v = v.astype(f32)
    lo = lo.astype(f32)
    hi = hi.astype(f32)
    rowp = rowp.astype(f32)
    cvec = cvec.astype(f32)
    scal = scal.astype(f32)
    inv_u = 1.0 / usage.astype(f32)
    ju = jobs.astype(f32) * inv_u
    isb = rowp[8:9]
    refs, lam_eq, stepw = rowp[9:10], rowp[10:11], rowp[11:12]
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
    return x, m.astype(mdt), v.astype(vdt)


def al_step_ref(x, m, v, usage, jobs, lo, hi, rowp, cvec, scal, **kw):
    """`al_step_tm` on the system's (W, T) layout; returns (x, m, v).

    x/usage/jobs/lo/hi/m/v: (W, T); rowp: (W, 12); cvec: (1, T)
    (−car_norm·mci), or (W, T) for per-workload carbon weights; scal:
    (1, 8). Transposes at entry and exit; keywords as `al_step_tm`.
    """
    out = al_step_tm(x.T, m.T, v.T, usage.T, jobs.T, lo.T, hi.T, rowp.T,
                     cvec.T, scal, **kw)
    return tuple(a.T for a in out)
