"""Pallas TPU kernel: chunked linear-recurrence scan (rwkv6 / SSM decode).

The matrix-state recurrence shared by RWKV6 ("Finch", data-dependent decay)
and Mamba-style SSD heads:

    S_t = diag(w_t) @ S_{t-1} + k_t^T v_t          S: [Dk, Dv]
    o_t = r_t @ (S_{t-1} + diag(u) @ (k_t^T v_t))  (u = bonus; None for SSM)

Grid (BH, T/C): the time axis is innermost and *sequential*; the state S
persists in VMEM scratch across chunk steps (the same cross-grid-step
scratch discipline as flash attention's running softmax).  Within a chunk
the recurrence is an unrolled fori over C steps of rank-1 updates — the
chunk lives entirely in VMEM (C=128, D=64 f32: 32 KB per tensor).

This is the TPU adaptation of the GPU "chunked parallel scan": the
inter-chunk dependency is irreducibly sequential; the intra-chunk work is
what the VPU parallelizes (vectorized over Dk x Dv).  A matmul
(intra-chunk-attention) formulation is a further MXU optimization recorded
in EXPERIMENTS.md §Perf.

Mosaic-ready by construction: rank-3 BlockSpecs/out_shape, no iota at all
(time stepping indexes the refs with ``pl.ds``), rank-1-free dot_generals
with explicit ``preferred_element_type``, and grid dimension semantics
(BH parallel, the chunk axis ``arbitrary`` — the carried state scratch
makes it sequential).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(chunk, use_bonus, r_ref, k_ref, v_ref, w_ref, u_ref, o_ref,
            state_ref):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    u = u_ref[0]        # [1, Dk] bonus (rwkv6) — zeros for plain SSM

    def step(t, s):
        # time stepping slices the refs (``pl.ds``): Mosaic lowers a
        # dynamic ref index, not ``dynamic_slice`` on loaded values
        row = (0, pl.ds(t, 1), slice(None))
        kt = k_ref[row]                                     # [1, Dk]
        vt = v_ref[row]                                     # [1, Dv]
        rt = r_ref[row]                                     # [1, Dk]
        wt = w_ref[row]                                     # [1, Dk]
        # outer product k_t^T v_t on the MXU: contract the length-1 time dim
        kv = jax.lax.dot_general(kt, vt, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [Dk, Dv]
        if use_bonus:
            att = s + u.T * kv
        else:
            att = s
        ot = jax.lax.dot_general(rt, att, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [1, Dv]
        o_ref[row] = ot.astype(o_ref.dtype)
        return wt.T * s + kv

    state_ref[...] = jax.lax.fori_loop(0, chunk, step, state_ref[...])


def pallas_specs(bh: int, t: int, dk: int, dv: int, chunk: int,
                 dtype=jnp.float32):
    """Grid/Block/out structure, shared with the lowering lint."""
    return dict(
        grid=(bh, t // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, dk), lambda b, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, dv), dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )


def linear_scan(r, k, v, w, u=None, *, chunk: int = 64,
                interpret: bool = False):
    """r/k/w: [BH, T, Dk]; v: [BH, T, Dv]; u: [BH, Dk] or None."""
    bh, t, dk = r.shape
    dv = v.shape[-1]
    chunk = min(chunk, t)
    assert t % chunk == 0
    use_bonus = u is not None
    if u is None:
        u = jnp.zeros((bh, dk), r.dtype)
    u = u[:, None, :]  # [BH, 1, Dk]

    kern = functools.partial(_kernel, chunk, use_bonus)
    return pl.pallas_call(
        kern,
        **pallas_specs(bh, t, dk, dv, chunk, r.dtype),
        interpret=interpret,
        name="linear_scan",
    )(r, k, v, w, u)
