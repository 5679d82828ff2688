"""Pallas TPU kernel for the fused CR1/CR2 AL inner step.

The engine's hot loop (`engine.al_minimize`) re-dispatches a chain of
~10 small elementwise/reduce ops per projected-Adam step — each one a
round-trip of (W, T) intermediates through HBM. This kernel fuses one
full inner step on a workload block held in VMEM:

  1. analytic augmented-Lagrangian gradient (RTS cubic + hinged batch
     queue-integral penalties, CR1 fixed-weight or CR2 multiplier form),
  2. bias-corrected Adam moment update,
  3. the box + day-mean-preserving projection,

and unrolls `k_steps` of them per invocation, so x and the Adam moments
(m, v) never leave VMEM between steps. `al_minimize`'s inner scan then
makes `inner_steps / k_steps` kernel calls instead of dispatching
`inner_steps × ~10` ops.

Layout: time-major (T, W). A grid step takes a (T, block_w) block with
workloads on the 128 lanes, so every lane holds a workload, and every
sum over hours is a sum down the sublanes (`ref.py`): vector adds, no
matmul. T is the block's full first dimension, so it need not be a
multiple of 8: the v5e compile is checked ahead of time at T = 30, 47
and 48 (`tests/test_tpu_compile.py`). The step math is
`ref.al_step_tm` itself, so kernel-vs-oracle parity isolates what Pallas
adds (tiling, padding, VMEM residency); see the note there on why the
hinge subgradient makes formulation-level diffs chaotic.

Packed-parameter layout (`rowp` (12, W), `scal` (1, 8)) is documented in
`ref.py`; `ops.kernel_layout` sets the layout, once per solve in
`ops.make_fused_inner`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.al_step.ref import al_step_tm

#: Lanes of one vreg: a block's width is a multiple of it.
LANES = 128
#: Default workload block per grid step. One call at W = 100k, T = 48,
#: timed on the host clock on a v5e: 2.08 ms, against 2.14 at 256 and
#: 2.46 at 1024.
BLOCK_W = 512


def _al_step_kernel(x_ref, m_ref, v_ref, u_ref, j_ref, lo_ref, hi_ref,
                    rowp_ref, cvec_ref, scal_ref, xo_ref, mo_ref, vo_ref,
                    **kw):
    xo_ref[...], mo_ref[...], vo_ref[...] = al_step_tm(
        x_ref[...], m_ref[...], v_ref[...], u_ref[...], j_ref[...],
        lo_ref[...], hi_ref[...], rowp_ref[...], cvec_ref[...],
        scal_ref[...], **kw)


def al_step_pallas(x, m, v, usage, jobs, lo, hi, rowp, cvec, scal, *,
                   mode: str, k_steps: int, beta1: float = 0.9,
                   beta2: float = 0.999, eps: float = 1e-8,
                   day_hours: int = 24, block_w: int | None = None,
                   interpret: bool | None = None):
    """`k_steps` fused AL inner steps on time-major tiles; returns
    (x, m, v).

    Same semantics as `ref.al_step_tm`, on (T, Wp) arrays whose Wp is a
    multiple of `block_w` (itself a multiple of 128): `ops.kernel_layout`
    lays the fleet out, once per solve in `ops.make_fused_inner`, so no
    call pads or slices. `cvec` may be (T, 1) (fleet-global carbon term, replicated to
    every block) or (T, Wp) (per-workload carbon weights, tiled like x).
    `block_w=None` picks min(BLOCK_W, Wp). `interpret=None` resolves
    backend-aware via `repro.kernels.dispatch.interpret_default`.
    """
    if interpret is None:
        from repro.kernels.dispatch import interpret_default
        interpret = interpret_default()
    T, Wp = x.shape
    if block_w is None:
        block_w = min(BLOCK_W, Wp)
    if block_w % LANES or Wp % block_w:
        raise ValueError(f"workloads ({Wp}) must fill whole blocks of a "
                         f"multiple of {LANES} lanes; block_w={block_w}")
    kern = functools.partial(_al_step_kernel, mode=mode, k_steps=k_steps,
                             beta1=beta1, beta2=beta2, eps=eps,
                             day_hours=day_hours)

    def lanes(rows):
        return pl.BlockSpec((rows, block_w), lambda i: (0, i))

    def rep(rows, cols):
        return pl.BlockSpec((rows, cols), lambda i: (0, 0))

    cvec_spec = rep(T, 1) if cvec.shape[1] == 1 else lanes(T)
    return pl.pallas_call(
        kern,
        grid=(Wp // block_w,),
        in_specs=[lanes(T)] * 7 + [lanes(rowp.shape[0]), cvec_spec,
                                   rep(1, 8)],
        out_specs=[lanes(T)] * 3,
        out_shape=[jax.ShapeDtypeStruct((T, Wp), jnp.float32),
                   jax.ShapeDtypeStruct((T, Wp), m.dtype),
                   jax.ShapeDtypeStruct((T, Wp), v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, m, v, usage, jobs, lo, hi, rowp, cvec, scal)
