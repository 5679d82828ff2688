"""Pallas TPU kernel for the Mamba-2 SSD inter-chunk state recurrence.

The chunked SSD algorithm (repro.models.ssm.ssd_chunked) has one sequential
component: h_c = decay_c · h_{c-1} + S_c over chunks. In jnp this is a
lax.scan whose (B, H, P, N) carry round-trips through HBM every chunk; here
the carry lives in VMEM scratch for the whole sweep — the grid's chunk axis
is "arbitrary" (sequential) and the (B, H) axes are parallel.

Each program owns one (head, batch) state tile of (P, N) = (64, 128) fp32 =
32 KB — far under VMEM, so many heads pipeline concurrently.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_scan_kernel(states_ref, decay_ref, hprev_ref, hlast_ref, h_ref):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    h = h_ref[...]
    hprev_ref[0, 0, 0] = h.astype(hprev_ref.dtype)
    dec = decay_ref[0, 0, 0].astype(jnp.float32)
    h_ref[...] = h * dec + states_ref[0, 0, 0].astype(jnp.float32)

    @pl.when(ic == nc - 1)
    def _final():
        hlast_ref[0, 0] = h_ref[...].astype(hlast_ref.dtype)


def ssd_scan_pallas(states, chunk_decay, interpret: bool = True):
    """states: (B, NC, H, P, N); chunk_decay: (B, NC, H) -> (h_prev, h_last)
    with h_prev (B, NC, H, P, N), h_last (B, H, P, N)."""
    B, NC, H, P, N = states.shape
    # decay broadcast to (B, NC, H, 1, 1) lanes for BlockSpec tiling.
    dec = chunk_decay[..., None, None]
    hprev, hlast = pl.pallas_call(
        _ssd_scan_kernel,
        grid=(B, H, NC),
        in_specs=[
            pl.BlockSpec((1, 1, 1, P, N), lambda b, h, c: (b, c, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, 1), lambda b, h, c: (b, c, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, P, N), lambda b, h, c: (b, c, h, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, NC, H, P, N), jnp.float32),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(states, dec)
    return hprev, hlast
