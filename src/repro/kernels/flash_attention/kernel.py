"""Pallas TPU flash-attention (FA2-style) kernel.

Grid: (B, H, num_q_blocks, num_kv_blocks) — the kv dimension is innermost
("arbitrary" semantics) and carries running max / denominator / accumulator
in VMEM scratch across its iterations. Causal blocks that are fully masked
are skipped with pl.when. BlockSpecs tile (S, Dh) into (block_q, Dh) /
(block_k, Dh) VMEM-resident tiles; Dh is always ≤ 256 so a (128, Dh) tile is
well within VMEM, and block sizes are multiples of the 128-lane MXU width.

Validated in interpret mode against `ref.attention_ref` (CPU container);
TPU is the compilation target.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               causal: bool, scale: float, block_q: int, block_k: int,
               seq_q: int, seq_kv: int, q_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kv_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    # Skip blocks that are entirely above the causal diagonal.
    first_q = q_offset + iq * block_q
    last_q = first_q + block_q - 1
    first_kv = ik * block_k
    run = (first_kv <= last_q) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        dead = kv_pos >= seq_kv
        if causal:
            dead = dead | (kv_pos > q_pos)
        s = jnp.where(dead, NEG_INF, s)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           block_q: int = 128, block_k: int = 128,
                           q_offset: int = 0, interpret: bool = True):
    """q: (B, H, Sq, Dh); k/v: (B, KV, Skv, Dh/Dv). Returns (B, H, Sq, Dv).

    H % KV == 0 (GQA). Sequences are padded to block multiples here and
    un-padded on return; masking handles the tail.
    """
    B, H, Sq, Dh = q.shape
    _, KV, Skv, _ = k.shape
    Dv = v.shape[-1]
    groups = H // KV
    scale = 1.0 / math.sqrt(Dh)
    pq = (-Sq) % block_q
    pk = (-Skv) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq = qp.shape[2] // block_q
    nk = kp.shape[2] // block_k

    kernel = functools.partial(
        _fa_kernel, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, seq_q=Sq, seq_kv=Skv, q_offset=q_offset)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, Dh),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, Dh),
                         lambda b, h, iq, ik, g=groups: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, Dv),
                         lambda b, h, iq, ik, g=groups: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dv),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * block_q, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :Sq]
