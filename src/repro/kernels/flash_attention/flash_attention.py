"""Pallas TPU kernel: blocked online-softmax attention (LM hot loop).

Flash-style forward: grid (batch*q_heads, Sq/BQ, Skv/BK) with the KV axis
innermost; running (max, denom, acc) live in VMEM scratch across KV steps.
Supports causal masking, sliding-window (gemma3 local layers: the window is
a WA=1/WS=window STRETCH sliding window over sequence "time"), and decode
(Sq=1 against a long KV cache).  GQA is handled by the ops wrapper (KV head
indexed q_head // group).

Tiling: per step VMEM holds (BQ,D) q + (BK,D) k,v + (BQ,BK) logits +
(BQ,D) acc — e.g. BQ=BK=512, D=128 f32: ~1.8 MB, well under VMEM; matmul
dims are 128-aligned for the MXU.

Mosaic-ready by construction (ISSUE 5): every BlockSpec/out_shape is
rank-3, the position masks use 2-D ``broadcasted_iota`` only, and the grid
carries explicit dimension semantics — (batch*head, q) parallel, kv
``arbitrary`` (the running-softmax scratch makes it sequential).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(scale, causal, window, blk_q, blk_k, seq_kv,
            q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                      # [BQ, D]
    k = k_ref[0]                      # [BK, D]
    v = v_ref[0]                      # [BK, D]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    k_pos = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    # decode offsets: q positions sit at the end of the KV timeline
    q_pos = q_pos + (seq_kv - pl.num_programs(1) * blk_q)
    mask = jnp.ones((blk_q, blk_k), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def pallas_specs(bh: int, sq: int, skv: int, d: int, blk_q: int, blk_k: int,
                 dtype=jnp.float32):
    """Grid/Block/out structure, shared with the lowering lint."""
    return dict(
        grid=(bh, sq // blk_q, skv // blk_k),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),   # running max
            pltpu.VMEM((blk_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((blk_q, d), jnp.float32),   # running accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    blk_q: int = 128, blk_k: int = 128,
                    interpret: bool = False):
    """q: [BH, Sq, D]; k, v: [BH, Skv, D] (KV already GQA-expanded)."""
    bh, sq, d = q.shape
    _, skv, _ = k.shape
    blk_q = min(blk_q, sq)
    blk_k = min(blk_k, skv)
    assert sq % blk_q == 0 and skv % blk_k == 0
    scale = d ** -0.5

    kern = functools.partial(_kernel, scale, causal, window, blk_q, blk_k, skv)
    return pl.pallas_call(
        kern,
        **pallas_specs(bh, sq, skv, d, blk_q, blk_k, q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
