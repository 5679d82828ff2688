"""Pallas TPU kernel for the fused CR1/CR2 AL inner step.

The engine's hot loop (`engine.al_minimize`) re-dispatches a chain of
~10 small elementwise/reduce ops per projected-Adam step — each one a
round-trip of (W, T) intermediates through HBM. This kernel fuses one
full inner step on a (block_w, T) workload tile held in VMEM:

  1. analytic augmented-Lagrangian gradient (RTS cubic + hinged batch
     queue-integral penalties, CR1 fixed-weight or CR2 multiplier form),
  2. bias-corrected Adam moment update,
  3. the box + day-mean-preserving projection,

and unrolls `k_steps` of them per invocation, so x and the Adam moments
(m, v) never leave VMEM between steps. `al_minimize`'s inner scan then
makes `inner_steps / k_steps` kernel calls instead of dispatching
`inner_steps × ~10` ops.

The day-mean projection is expressed as two matmuls against a static
(n_days, T) day-membership mask built with `broadcasted_iota` — no
reshapes, which the TPU vector layout dislikes. The gradient/projection
math is imported from `ref.py` so kernel-vs-oracle parity isolates what
Pallas adds (tiling, padding, VMEM residency); see the note there on
why the hinge subgradient makes formulation-level diffs chaotic.

Packed-parameter layout (`rowp` (W, 12), `scal` (1, 8)) is documented in
`ref.py`; `ops.pack_rows` builds the static row block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.al_step.ref import _pen_and_grad, _project


def _al_step_kernel(x_ref, m_ref, v_ref, u_ref, j_ref, lo_ref, hi_ref,
                    rowp_ref, cvec_ref, scal_ref, xo_ref, mo_ref, vo_ref,
                    *, mode: str, k_steps: int, beta1: float, beta2: float,
                    eps: float, day_hours: int):
    f32 = jnp.float32
    x = x_ref[...].astype(f32)
    m = m_ref[...].astype(f32)
    v = v_ref[...].astype(f32)
    u = u_ref[...].astype(f32)
    lo = lo_ref[...].astype(f32)
    hi = hi_ref[...].astype(f32)
    rowp = rowp_ref[...].astype(f32)
    cvec = cvec_ref[...].astype(f32)
    scal = scal_ref[...].astype(f32)

    inv_u = 1.0 / u
    ju = j_ref[...].astype(f32) * inv_u
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

    xo_ref[...] = x
    mo_ref[...] = m.astype(mo_ref.dtype)
    vo_ref[...] = v.astype(vo_ref.dtype)


def al_step_pallas(x, m, v, usage, jobs, lo, hi, rowp, cvec, scal, *,
                   mode: str, k_steps: int, beta1: float = 0.9,
                   beta2: float = 0.999, eps: float = 1e-8,
                   day_hours: int = 24, block_w: int | None = None,
                   interpret: bool | None = None):
    """`k_steps` fused AL inner steps on (W, T) tiles; returns (x, m, v).

    Same signature/semantics as `ref.al_step_ref` plus tiling knobs.
    Padding: W to block_w — usage pads with ones (no 0/0), lo = hi = 0
    pins padded rows at zero, rowp pads with zeros (k = 0 ⇒ no penalty).
    `cvec` may be (1, T) (fleet-global carbon term, replicated to every
    tile) or (W, T) (per-row carbon weights, tiled like x and zero-padded
    — padded rows are pinned anyway). `block_w=None` picks min(128, W
    rounded up to 16) — the bf16 sublane floor, so bf16 moment tiles stay
    legal. `interpret=None` resolves backend-aware via
    `repro.kernels.dispatch.interpret_default`.
    """
    if interpret is None:
        from repro.kernels.dispatch import interpret_default
        interpret = interpret_default()
    W, T = x.shape
    if block_w is None:
        block_w = min(128, -(-W // 16) * 16)
    pw = (-W) % block_w

    def pad(a, cv=0.0):
        return jnp.pad(a, ((0, pw), (0, 0)), constant_values=cv)

    nw = (W + pw) // block_w
    kern = functools.partial(_al_step_kernel, mode=mode, k_steps=k_steps,
                             beta1=beta1, beta2=beta2, eps=eps,
                             day_hours=day_hours)

    def row(cols):
        return pl.BlockSpec((block_w, cols), lambda i: (i, 0))

    def rep(cols):
        return pl.BlockSpec((1, cols), lambda i: (0, 0))

    if cvec.shape[0] == 1:
        cvec_spec = rep(T)
    else:
        cvec_spec, cvec = row(T), pad(cvec)

    out = pl.pallas_call(
        kern,
        grid=(nw,),
        in_specs=[row(T)] * 7 + [row(rowp.shape[1]), cvec_spec, rep(8)],
        out_specs=[row(T)] * 3,
        out_shape=[jax.ShapeDtypeStruct((W + pw, T), jnp.float32),
                   jax.ShapeDtypeStruct((W + pw, T), m.dtype),
                   jax.ShapeDtypeStruct((W + pw, T), v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(pad(x), pad(m), pad(v), pad(usage, 1.0), pad(jobs), pad(lo),
      pad(hi), pad(rowp), cvec, scal)
    return out[0][:W], out[1][:W], out[2][:W]
