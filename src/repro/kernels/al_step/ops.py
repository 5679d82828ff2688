"""Chunked dispatch for the fused AL inner-step kernel.

`make_fused_inner` packages a fleet's static arrays into the kernel's
layout once, and returns a `fused_inner(x, lam_eq, lam_in, mu)` callback
for `engine.al_minimize`: a `lax.scan` of `inner_steps / k_steps` kernel
invocations carrying (x, m, v, t) — the Adam step count threads through
so bias correction is identical to one long loop. Fresh (zero) moments
per call match the engine contract (moments reset every outer
multiplier round).

The kernel is time-major: (T, Wp), workloads on the lanes, W padded to
whole blocks (see `kernel.py`). The fleet constants are transposed and
padded once, when the callback is built; each callback call transposes
and pads x once on the way in and slices it back once on the way out,
so no kernel call inside the scan pads or slices.

Everything here is pure jnp + `pallas_call`, so the callback is safe
under `jit`, `vmap` (λ/cap sweeps and scenario ensembles batch the
packed scalars), and inside `shard_map` bodies (each device runs the
kernel on its local row block, so the transposes stay local).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.al_step.kernel import BLOCK_W, LANES, al_step_pallas
from repro.kernels.al_step.ref import al_step_tm
from repro.kernels.dispatch import interpret_default


def pack_rows(rts_coeffs, betas, k, x2_kind, is_batch, refs=None):
    """Per-workload penalty parameters -> the (W, 10) static row block
    (rows 0-9 of the kernel's `rowp`, transposed; see `ref.py` for the
    layout). `refs=None` fills zeros (CR1 has no per-row reference)."""
    f32 = jnp.float32
    k = jnp.asarray(k, f32)[:, None]
    x2 = jnp.asarray(x2_kind, f32)[:, None]
    isb = jnp.asarray(is_batch, f32)[:, None]
    r = (jnp.zeros_like(k) if refs is None
         else jnp.asarray(refs, f32)[:, None])
    return jnp.concatenate([jnp.asarray(rts_coeffs, f32),
                            jnp.asarray(betas, f32), k, x2, isb, r], axis=1)


def time_major(a, wp, fill=0.0):
    """(W, C) -> (C, wp): transposed, workloads padded to `wp` with
    `fill`."""
    a = jnp.asarray(a).T
    return jnp.pad(a, ((0, 0), (0, wp - a.shape[1])), constant_values=fill)


def kernel_layout(usage, jobs, lo, hi, rows, cvec, block_w=None):
    """The kernel's time-major layout of a fleet's constants.

    usage/jobs/lo/hi: (W, T); rows: (W, R) row parameters; cvec: (1, T)
    or (W, T). W pads to whole blocks of `block_w` lanes (None picks
    min(`kernel.BLOCK_W`, W rounded up to 128)): usage with ones (no
    0/0), the rest with zeros (lo = hi = 0 pins a padded workload at
    zero, k = 0 gives it no penalty). A (1, T) cvec becomes (T, 1),
    replicated to every block.

    Returns ((usage, jobs, lo, hi, rows, cvec) time-major, block_w, wp);
    lay x, m and v out with `time_major(a, wp)`.
    """
    W = usage.shape[0]
    if block_w is None:
        block_w = min(BLOCK_W, -(-W // LANES) * LANES)
    wp = -(-W // block_w) * block_w
    cvec = jnp.asarray(cvec, jnp.float32)
    cvec_t = cvec.T if cvec.shape[0] == 1 else time_major(cvec, wp)
    consts = (time_major(usage, wp, 1.0), time_major(jobs, wp),
              time_major(lo, wp), time_major(hi, wp), time_major(rows, wp),
              cvec_t)
    return consts, block_w, wp


def make_fused_inner(usage, jobs, lo, hi, row_base, cvec, *, mode: str,
                     cfg, step_scale, coef0=0.0, scale=None,
                     k_steps: int = 8, block_w: int | None = None,
                     interpret: bool | None = None, use_ref: bool = False,
                     day_hours: int = 24):
    """Build the `fused_inner` hook for `engine.al_minimize`.

    Args:
      usage/jobs/lo/hi: (W, T) fleet constants (bounds from
        `fleet_solver._bounds`).
      row_base: (W, 10) from `pack_rows` (CR2 passes `refs` there).
      cvec: (1, T) carbon gradient term, i.e. `-car_norm * mci[None, :]`,
        or (W, T) per-row carbon weights (multi-region fleets).
      mode: "cr1" (fixed penalty weight `coef0 = lam * pen_norm`) or
        "cr2" (equality-multiplier form; needs `scale`).
      cfg: `EngineConfig` — supplies inner_steps, lr, betas, eps and the
        moment storage dtype.
      step_scale: the adapter's step scale (multiplies cfg.lr). A scalar
        folds into the packed `lr_scale` and rowp row 11 packs ones
        (bitwise the scalar kernel: x·1.0 is exact); a (W, 1) per-row
        vector rides in row 11 with `lr_scale = cfg.lr`.
      k_steps: fused steps per kernel invocation; `inner_steps` need not
        divide evenly — the remainder runs as one short call.
      block_w: workloads per grid step (a multiple of 128); None as in
        `kernel_layout`.
      use_ref: route through the jnp oracle `ref.al_step_tm` instead of
        Pallas (parity harnesses; identical layout and call structure).

    Layout and padding: `kernel_layout`, once, when the callback is
    built.

    The returned callback runs exactly `cfg.inner_steps` projected-Adam
    steps from zero moments and returns the new x (W, T) f32.
    """
    W, T = usage.shape
    f32 = jnp.float32
    mdt = jnp.dtype(cfg.moment_dtype)
    inv_scale = 0.0 if scale is None else 1.0 / scale
    if jnp.ndim(step_scale) == 0:
        lr_scale = cfg.lr * step_scale
        step_col = jnp.ones((W, 1), f32)
    else:
        lr_scale = jnp.asarray(cfg.lr, f32)
        step_col = jnp.asarray(step_scale, f32).reshape(W, 1)
    # rowp with row 10 (lam_eq) zero: CR1's as it stands, CR2's once
    # each call fills in its multipliers.
    rows = jnp.concatenate([row_base, jnp.zeros((W, 1), f32), step_col],
                           axis=1)
    consts, block_w, wp = kernel_layout(usage, jobs, lo, hi, rows, cvec,
                                        block_w)
    usage_t, jobs_t, lo_t, hi_t, rows_t, cvec_t = consts
    steps = int(cfg.inner_steps)
    k_steps = max(1, min(int(k_steps), steps))
    n_full, rem = divmod(steps, k_steps)
    if not use_ref:
        interpret = interpret_default(interpret)

    def call(x, m, v, rowp, mu, t0, n):
        vals = (coef0, mu, inv_scale, lr_scale, t0, 0.0, 0.0, 0.0)
        scal = jnp.stack([jnp.asarray(s, jnp.float32).reshape(())
                          for s in vals]).reshape(1, 8)
        args = (x, m, v, usage_t, jobs_t, lo_t, hi_t, rowp, cvec_t, scal)
        kw = dict(mode=mode, k_steps=n, beta1=cfg.beta1, beta2=cfg.beta2,
                  eps=cfg.eps, day_hours=day_hours)
        if use_ref:
            return al_step_tm(*args, **kw)
        return al_step_pallas(*args, block_w=block_w, interpret=interpret,
                              **kw)

    def fused_inner(x, lam_eq, lam_in, mu):
        del lam_in  # CR1/CR2 carry no inequality multipliers
        rowp = rows_t
        if mode == "cr2":
            lam_t = time_major(lam_eq.astype(f32).reshape(W, 1), wp)
            rowp = jnp.concatenate([rows_t[:10], lam_t, rows_t[11:]])
        m0 = jnp.zeros((T, wp), mdt)
        v0 = jnp.zeros((T, wp), mdt)

        def chunk(c, _):
            xx, mm, vv, t0 = c
            xx, mm, vv = call(xx, mm, vv, rowp, mu, t0, k_steps)
            return (xx, mm, vv, t0 + jnp.asarray(k_steps, f32)), None

        c = (time_major(x.astype(f32), wp), m0, v0, jnp.asarray(0.0, f32))
        if n_full:
            c, _ = jax.lax.scan(chunk, c, None, length=n_full)
        xx, mm, vv, t0 = c
        if rem:
            xx, _, _ = call(xx, mm, vv, rowp, mu, t0, rem)
        return xx[:, :W].T

    return fused_inner
