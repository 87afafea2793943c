"""Pallas TPU kernel: keyed windowed segment-reduce (paper Q1 hot loop).

The wordcount/paircount update phase: N (tuple-hit, key, slot) records are
reduced into the [K, S, W] window-state accumulator.  Intra-chip VSN again:
each grid step owns one contiguous tile of virtual-key rows and accumulates
only the records whose key falls in it -- the shared-read/disjoint-write
discipline of Theorem 3, with zero scatter conflicts by construction.

The work grows with the hits, not with keys x hits.  Before the kernel the
wrapper groups the tick's hits by their flattened (key, slot) cell
``key * S + slot`` in XLA: one ``lax.sort`` with the values riding as extra
operands.  A hit whose key is outside ``[0, K)`` or whose slot is outside
``[0, S)`` is dead: it takes the sentinel cell ``K * S``, sorts after every
live hit and is never visited.  ``searchsorted`` at the tile boundaries
``t * TK * S`` gives each key tile its ``[start, end)`` range of sorted
hits, and from those the wrapper builds the visit schedule: the list of
(key tile, hit block) pairs that hold hits, tile-major, plus one visit
for each tile that holds none (so every tile is visited at least once,
and the output needs no aliasing of ``acc``).  Its static length is
``n_tiles + n_blocks - 1``, against the ``n_tiles x n_blocks`` of a full
product; visits past the real count repeat the last real pair and do no
work, so no output block is written back twice with different contents.
The schedule reaches the kernel as scalar prefetch
(``pltpu.PrefetchScalarGridSpec``), the shape of megablox's
``make_group_metadata``.

Mosaic layout: everything is lane-major.  The accumulator is handled as
``[W, K*S]`` (flattened cells on lanes; the wrapper transposes sigma's
``[K, S, W]`` in and out), the sorted cells enter as ``(1, N)`` and the
values as ``[W, N]``, and the 1-D grid walks the visits (``arbitrary``:
a tile's visits are consecutive and its output tile stays resident
across them).  Per visit the program builds the ``(TK*S, BN)``
cell-by-hit one-hot with a rank-2 ``broadcasted_iota`` and contracts the
hit lanes against the values on the MXU (an NT ``dot_general``, exact at
``HIGHEST`` precision), which yields the lane-major ``(W, TK*S)``
contribution directly.  A hit of a neighbouring tile (or a dead one) in
the same block falls outside the tile's rows and matches none.  The
output tile is seeded from ``acc`` on the tile's first visit.  VMEM and
code size per step are bounded by ``TK*S x BN`` whatever the tick size.

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

from repro import obs as _obs

BLOCK_N = 512                   # hit lanes per grid step
LANES = 128                     # hit-block lane quantum


def _geometry(n: int, k: int, tile_k: int):
    """-> (tile_k, block_n, n_blocks, n_tiles) of a call with N hits."""
    tile_k = min(tile_k, k)
    # lane-aligned hit blocks; the padding lanes are dead
    block_n = min(BLOCK_N, -(-n // LANES) * LANES)
    return tile_k, block_n, -(-n // block_n), k // tile_k


def grid_steps(n: int, k: int, tile_k: int = 128):
    """-> (visits, dense_steps): the static length of the kernel's visit
    grid for N hits into K keys, and the key tiles x hit blocks that a
    grid over their full product would run."""
    _, _, n_blocks, n_tiles = _geometry(n, k, tile_k)
    return n_tiles + n_blocks - 1, n_tiles * n_blocks


def _schedule(offsets, block_n: int, n_blocks: int, visits: int):
    """Tile-major (tile, block) visits from the per-tile hit offsets.

    A tile with hits in ``[start, end)`` visits the blocks from
    ``start // BN`` to ``(end - 1) // BN``; a tile with none visits one
    block (near its offset, so the DMA mostly repeats) and does no work.
    Consecutive tiles share at most one block, so the real count is at
    most ``n_tiles + n_blocks - 1 = visits``; the rest repeat the last
    real pair."""
    start, end = offsets[:-1], offsets[1:]
    n_tiles = start.shape[0]
    first = jnp.minimum(start // block_n, n_blocks - 1)
    last = jnp.where(end > start, (end - 1) // block_n, first)
    per_tile = last - first + 1
    n_real = jnp.sum(per_tile)
    tile_ids = jnp.repeat(jnp.arange(n_tiles, dtype=jnp.int32), per_tile,
                          total_repeat_length=visits)
    v = jnp.minimum(jnp.arange(visits, dtype=jnp.int32), n_real - 1)
    tile_start = jnp.cumsum(per_tile) - per_tile
    block_ids = first[tile_ids] + v - tile_start[tile_ids]
    return tile_ids, block_ids.astype(jnp.int32), n_real.reshape(1)


def _kernel(n_slots, tile_k, tile_ids, block_ids, offsets, n_real,
            cells_ref, vals_ref, acc_ref, out_ref):
    v = pl.program_id(0)
    t = tile_ids[v]

    @pl.when((v == 0) | (t != tile_ids[jnp.maximum(v - 1, 0)]))
    def _seed():
        out_ref[...] = acc_ref[...]

    @pl.when((v < n_real[0]) & (offsets[t + 1] > offsets[t]))
    def _accumulate():
        cells = cells_ref[...]                        # [1, BN], sorted
        local = cells - t * (tile_k * n_slots)        # cell within the tile
        rows = jax.lax.broadcasted_iota(
            jnp.int32, (tile_k * n_slots, cells.shape[1]), 0)
        onehot = (rows == local).astype(jnp.float32)  # [TK*S, BN]
        out_ref[...] += jax.lax.dot_general(
            vals_ref[...], onehot, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)       # [W, TK*S]


def pallas_specs(n: int, w: int, k: int, s: int, tile_k: int,
                 block_n: int, dtype=jnp.float32):
    """Grid/Block/out structure for ``n`` (padded) hits, shared with the
    lowering lint.  Four scalar-prefetch operands (tile ids, block ids,
    per-tile hit offsets, real visit count) steer the block index maps;
    all blocks are rank 2."""
    cells = tile_k * s
    visits, _ = grid_steps(n, k, tile_k)

    def hits(v, tile_ids, block_ids, offsets, n_real):
        return 0, block_ids[v]

    def tile(v, tile_ids, block_ids, offsets, n_real):
        return 0, tile_ids[v]

    return dict(
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(visits,),
            in_specs=[pl.BlockSpec((1, block_n), hits),
                      pl.BlockSpec((w, block_n), hits),
                      pl.BlockSpec((w, cells), tile)],
            out_specs=pl.BlockSpec((w, cells), tile)),
        out_shape=jax.ShapeDtypeStruct((w, k * s), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )


def segment_aggregate(keys, slots, vals, acc, *, tile_k: int = 128,
                      interpret: bool = False):
    n, w = vals.shape
    k, s, w2 = acc.shape
    assert w == w2
    tile_k, block_n, n_blocks, n_tiles = _geometry(n, k, tile_k)
    assert k % tile_k == 0
    visits, dense_steps = grid_steps(n, k, tile_k)
    _obs.gauge_set("segment_aggregate.visits", visits)
    _obs.gauge_set("segment_aggregate.dense_steps", dense_steps)

    # group the hits by cell; dead hits carry the sentinel and value 0
    dead = k * s
    live = (keys >= 0) & (keys < k) & (slots >= 0) & (slots < s)
    cells = jnp.where(live, keys * s + slots, dead).astype(jnp.int32)
    vals = jnp.where(live[:, None], vals, 0).astype(acc.dtype)
    cells, *cols = jax.lax.sort((cells, *vals.T), num_keys=1,
                                is_stable=False)
    n_pad = n_blocks * block_n
    cells = jnp.pad(cells, (0, n_pad - n), constant_values=dead)
    vals = jnp.pad(jnp.stack(cols), ((0, 0), (0, n_pad - n)))
    offsets = jnp.searchsorted(
        cells, jnp.arange(n_tiles + 1, dtype=jnp.int32) * (tile_k * s)
    ).astype(jnp.int32)
    tile_ids, block_ids, n_real = _schedule(offsets, block_n, n_blocks,
                                            visits)

    kern = functools.partial(_kernel, s, tile_k)
    out = pl.pallas_call(
        kern,
        **pallas_specs(n_pad, w, k, s, tile_k, block_n, acc.dtype),
        interpret=interpret,
        name="segment_aggregate",
    )(tile_ids, block_ids, offsets, n_real, cells.reshape(1, n_pad), vals,
      acc.transpose(2, 0, 1).reshape(w, k * s))
    return out.reshape(w, k, s).transpose(1, 2, 0)
