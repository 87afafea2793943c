"""Pallas TPU kernel: keyed windowed segment-reduce (paper Q1 hot loop).

The wordcount/paircount update phase: N (tuple-hit, key, slot) records are
reduced into the [K, S, W] window-state accumulator.  Intra-chip VSN again:
the hit records live once in HBM (the shared tuple block); the grid programs
each own a contiguous tile of virtual-key rows and *scan the whole block*,
accumulating only the records whose key falls in their tile — the
shared-read/disjoint-write discipline of Theorem 3, with zero scatter
conflicts by construction (a scatter-free formulation: the gather+mask turns
the random scatter into dense VPU selects, which is the TPU-native shape of
the paper's per-key f_R loop).

Mosaic layout: everything is lane-major.  The accumulator is handled as
``[W, K*S]`` (flattened (key, slot) cells on lanes; the wrapper transposes
sigma's ``[K, S, W]`` in and out), the hits enter as ``(1, N)`` rows and
the values as ``[W, N]``, and the grid is 2-D: key tiles (``parallel``) x
hit blocks of ``BN`` lanes (``arbitrary``, innermost).  Per step the
program builds the ``(TK*S, BN)`` cell-by-hit one-hot with a rank-2
``broadcasted_iota`` and contracts the hit lanes against the values on the
MXU (an NT ``dot_general``, exact at ``HIGHEST`` precision), which yields
the lane-major ``(W, TK*S)`` contribution directly.  The output tile stays
resident across the hit blocks and accumulates; it is seeded from ``acc``
on the first block.  No rank-1 BlockSpecs, no 1-D iota, no ``(N, 1)``
columns (TPU layouts pad a minor dim of 1 to 128 lanes); VMEM and code
size per step are bounded by ``TK*S x BN`` whatever the tick size.

Shapes
  keys   i32[N]      virtual key per hit (-1 = dead lane)
  slots  i32[N]      window slot per hit
  vals   f32[N, W]   contribution (1.0 for counts)
  acc    f32[K, S, W]  accumulator
out
  acc'   f32[K, S, W]
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_N = 512                   # hit lanes per grid step
LANES = 128                     # hit-block lane quantum


def _kernel(n_slots, tile_k, keys_ref, slots_ref, vals_ref, acc_ref,
            out_ref):
    i = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _seed():
        out_ref[...] = acc_ref[...]

    keys = keys_ref[...]                  # [1, BN]
    local = keys - i * tile_k             # key row within this tile
    in_tile = (local >= 0) & (local < tile_k) & (keys >= 0)
    # flattened (key, slot) cell of each hit in this tile; -1 = elsewhere
    cell = jnp.where(in_tile, local * n_slots + slots_ref[...], -1)
    rows = jax.lax.broadcasted_iota(
        jnp.int32, (tile_k * n_slots, keys.shape[1]), 0)
    onehot = (rows == cell).astype(jnp.float32)       # [TK*S, BN]
    out_ref[...] += jax.lax.dot_general(
        vals_ref[...], onehot, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)           # [W, TK*S]


def pallas_specs(n: int, w: int, k: int, s: int, tile_k: int,
                 block_n: int, dtype=jnp.float32):
    """Grid/Block/out structure, shared with the lowering lint.  Hit blocks
    walk the inner grid axis; the accumulator tile walks the key axis and
    stays resident across the hit blocks.  All specs rank >= 2."""
    cells = tile_k * s
    return dict(
        grid=(k // tile_k, n // block_n),
        in_specs=[
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((w, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((w, cells), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((w, cells), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((w, k * s), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )


def segment_aggregate(keys, slots, vals, acc, *, tile_k: int = 128,
                      interpret: bool = False):
    n, w = vals.shape
    k, s, w2 = acc.shape
    assert w == w2
    tile_k = min(tile_k, k)
    assert k % tile_k == 0

    # lane-align the hit block: padding lanes carry key -1 (dead) and zero
    # contribution, so every backend reduces the identical value.
    block_n = min(BLOCK_N, -(-n // LANES) * LANES)
    n_pad = -(-n // block_n) * block_n
    if n_pad != n:
        keys = jnp.pad(keys, (0, n_pad - n), constant_values=-1)
        slots = jnp.pad(slots, (0, n_pad - n))
        vals = jnp.pad(vals, ((0, n_pad - n), (0, 0)))

    kern = functools.partial(_kernel, s, tile_k)
    out = pl.pallas_call(
        kern,
        **pallas_specs(n_pad, w, k, s, tile_k, block_n, acc.dtype),
        interpret=interpret,
        name="segment_aggregate",
    )(keys.reshape(1, n_pad), slots.reshape(1, n_pad), vals.T,
      acc.transpose(2, 0, 1).reshape(w, k * s))
    return out.reshape(w, k, s).transpose(1, 2, 0)
