"""Pallas TPU kernel for fleet-scale DR penalty features (Table IV).

The fleet solver evaluates the four queue-integral features for every
workload at every optimizer iteration — the hot loop when coordinating
thousands of jobs. The jnp path materializes four (W, T) cumsum
intermediates in HBM per evaluation; this kernel keeps a (block_w, T) tile
of workloads resident in VMEM and emits all four features in one pass
(arithmetic intensity: ~10 flops/byte on a (128, T=48→128-padded) tile,
bound by the single HBM read of d/usage/jobs).

Hours sit on the lane axis of the tile; the running sums are matmuls
against a triangular mask (`al_step.ref.prefix_sum`), the form Mosaic
lowers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.features import backlog_pos
from repro.kernels.al_step.ref import prefix_sum


def _queue(rate):
    return backlog_pos(prefix_sum(rate), rate).sum(axis=1, keepdims=True)


def _features_kernel(d_ref, u_ref, j_ref, o_ref):
    d = d_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    j = j_ref[...].astype(jnp.float32)
    o_ref[:, 0:1] = _queue(j * d / u)
    o_ref[:, 1:2] = _queue(d)
    o_ref[:, 2:3] = _queue(j * d * jnp.abs(d) / u)
    o_ref[:, 3:4] = (j * jnp.maximum(d, 0.0) / u).sum(axis=1, keepdims=True)


def dr_features_pallas(d, usage, jobs, block_w: int = 128,
                       interpret: bool | None = None):
    """d/usage/jobs: (W, T) -> (W, 4) feature matrix.

    Padding: W to block_w (zero rows are harmless — usage is padded with
    ones to avoid 0/0). `interpret=None` resolves backend-aware via
    `repro.kernels.dispatch.interpret_default`."""
    if interpret is None:
        from repro.kernels.dispatch import interpret_default
        interpret = interpret_default()
    W, T = d.shape
    pw = (-W) % block_w
    dp = jnp.pad(d, ((0, pw), (0, 0)))
    up = jnp.pad(usage, ((0, pw), (0, 0)), constant_values=1.0)
    jp = jnp.pad(jobs, ((0, pw), (0, 0)))
    nw = dp.shape[0] // block_w
    out = pl.pallas_call(
        _features_kernel,
        grid=(nw,),
        in_specs=[pl.BlockSpec((block_w, T), lambda i: (i, 0))] * 3,
        out_specs=pl.BlockSpec((block_w, 4), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((dp.shape[0], 4), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(dp, up, jp)
    return out[:W]
