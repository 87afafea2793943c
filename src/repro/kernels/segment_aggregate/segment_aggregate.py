"""Pallas TPU kernel: keyed windowed segment-reduce (paper Q1 hot loop).

The wordcount/paircount update phase: N (tuple-hit, key, slot) records are
reduced into the [K, S, W] window-state accumulator.  Intra-chip VSN again:
each grid step owns one contiguous tile of virtual-key rows and accumulates
only the records whose key falls in it -- the shared-read/disjoint-write
discipline of Theorem 3, with zero scatter conflicts by construction.

The work grows with the hits, not with the keys.  Before the kernel the
wrapper groups the tick's hits by their flattened (key, slot) cell
``key * S + slot`` in XLA: one ``lax.sort`` with the values riding as extra
operands.  A hit whose key is outside ``[0, K)`` or whose slot is outside
``[0, S)`` is dead: it takes the sentinel cell ``K * S``, sorts after every
live hit and is never visited.  The visit schedule is the list of
(key tile, hit block) pairs that hold hits, tile-major: a sorted hit opens
a visit where its tile or its block differs from the hit before it, and
the opening hits, found by a ``searchsorted`` on the running count of
openings, give each visit its tile and block.  A tile with no hits is
never visited: ``acc`` is aliased to the output, so its untouched tiles
keep their values in place, and nothing of the schedule is sized by the
key count.

The schedule reaches the kernel as scalar prefetch
(``pltpu.PrefetchScalarGridSpec``, the shape of megablox's
``make_group_metadata``) in chunks of at most ``CHUNK`` visits, one
``pallas_call`` a chunk inside a loop that runs as many chunks as the
call has visits (none for a call without live hits): the scalar memory
holds a chunk, never the whole bound.  A chunk's visits past the real
count repeat the last real pair and do no work, so no output block is
written back twice with different contents; a tile whose visits straddle
two chunks is written back by the first and read again by the second.

Mosaic layout: everything is lane-major, with the keys on lanes.  The
accumulator is handled as ``[S, W, K]``, the layout in which the chip
keeps sigma's ``[K, S, W]`` (keys minor, tiles of one row by 128 lanes),
so the view is free.  Keys, slots and values of the sorted hits enter as ``(1, N)``,
``(1, N)`` and ``[W, N]``, and the 1-D grid walks the visits
(``arbitrary``: a tile's visits are consecutive and its output tile stays
resident across them).  Per visit the program builds the ``(TK, BN)``
key-by-hit one-hot and the ``(S*W, BN)`` cell rows of the hits' values
with rank-2 ``broadcasted_iota``s, and contracts the hit lanes on the MXU
(an NT ``dot_general``, exact at ``HIGHEST`` precision), which yields the
``(S*W, TK)`` contribution, added slot by slot to the ``(S, W, TK)``
output tile.  A hit of a neighbouring tile (or a
dead one) in the same block falls outside the tile's keys and matches
none.  The output tile is seeded from ``acc`` on the tile's first visit
of a chunk.  VMEM and code size per step are bounded by ``TK x BN``
whatever the tick size.

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
CHUNK = 4096                    # visits per kernel call (scalar memory)


def _geometry(n: int, k: int, tile_k: int):
    """-> (tile_k, block_n, n_blocks, n_tiles) of a call with N hits."""
    tile_k = min(tile_k, k)
    # lane-aligned hit blocks; the padding lanes are dead
    block_n = min(BLOCK_N, -(-n // LANES) * LANES)
    return tile_k, block_n, -(-n // block_n), k // tile_k


def grid_steps(n: int, k: int, tile_k: int = 128):
    """-> (visits, dense_steps): the most (key tile, hit block) visits N
    hits into K keys can need -- hit tiles plus hit blocks, less one --
    and the key tiles x hit blocks of a grid over their full product."""
    _, _, n_blocks, n_tiles = _geometry(n, k, tile_k)
    return min(n_tiles, n) + n_blocks - 1, n_tiles * n_blocks


def _openings(cells, tile_cells: int, n_tiles: int, block_n: int):
    """Per sorted hit: its key tile (``n_tiles`` for a dead hit), and the
    running count of visits opened up to it: a live hit opens one where
    its tile or its block differs from the hit before it."""
    tile = cells // tile_cells
    block = jnp.arange(cells.shape[0], dtype=jnp.int32) // block_n
    new = (tile != jnp.roll(tile, 1)) | (block != jnp.roll(block, 1))
    new = new.at[0].set(True)
    opened = jnp.cumsum((new & (tile < n_tiles)).astype(jnp.int32))
    return tile, opened


def _chunk(tile, opened, n_real, c, block_n: int, chunk: int):
    """Tile and block of the visits ``[c*chunk, (c+1)*chunk)``, and how many
    of them are real; the rest repeat the last real pair."""
    v = jnp.minimum(c * chunk + jnp.arange(chunk, dtype=jnp.int32),
                    n_real - 1)
    hit = jnp.searchsorted(opened, v + 1, side="left").astype(jnp.int32)
    n_here = jnp.clip(n_real - c * chunk, 0, chunk)
    return tile[hit], hit // block_n, n_here.reshape(1)


def _kernel(n_slots, width, tile_k, tile_ids, block_ids, n_real,
            keys_ref, slots_ref, vals_ref, acc_ref, out_ref):
    v = pl.program_id(0)
    t = tile_ids[v]

    @pl.when((v == 0) | (t != tile_ids[jnp.maximum(v - 1, 0)]))
    def _seed():
        out_ref[...] = acc_ref[...]

    @pl.when(v < n_real[0])
    def _accumulate():
        local = keys_ref[...] - t * tile_k            # [1, BN] key in tile
        bn = local.shape[1]
        onehot = (jax.lax.broadcasted_iota(jnp.int32, (tile_k, bn), 0)
                  == local).astype(jnp.float32)       # [TK, BN]
        row = jax.lax.broadcasted_iota(jnp.int32, (n_slots * width, bn), 0)
        cell = slots_ref[...] * width                 # [1, BN]
        rows = jnp.zeros((n_slots * width, bn), jnp.float32)
        for w in range(width):
            rows = jnp.where(row == cell + w, vals_ref[w:w + 1, :], rows)
        part = jax.lax.dot_general(
            rows, onehot, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)       # [S*W, TK]
        for s in range(n_slots):
            out_ref[s] += part[s * width:(s + 1) * width]


def pallas_specs(n: int, w: int, k: int, s: int, tile_k: int,
                 block_n: int, dtype=jnp.float32):
    """Grid/Block/out structure of one chunk's call for ``n`` (padded)
    hits, shared with the lowering lint.  Three scalar-prefetch operands
    (tile ids, block ids, real visit count) steer the block index maps;
    the hit blocks are rank 2 and the accumulator's rank 3, and ``acc``
    (operand 6, after the three) is the output."""
    visits, _ = grid_steps(n, k, tile_k)

    def hits(v, tile_ids, block_ids, n_real):
        return 0, block_ids[v]

    def tile(v, tile_ids, block_ids, n_real):
        return 0, 0, tile_ids[v]

    return dict(
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(min(visits, CHUNK),),
            in_specs=[pl.BlockSpec((1, block_n), hits),
                      pl.BlockSpec((1, block_n), hits),
                      pl.BlockSpec((w, block_n), hits),
                      pl.BlockSpec((s, w, tile_k), tile)],
            out_specs=pl.BlockSpec((s, w, tile_k), tile)),
        out_shape=jax.ShapeDtypeStruct((s, w, k), dtype),
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )


def segment_aggregate(keys, slots, vals, acc, *, tile_k: int = 128,
                      interpret: bool = False):
    n, w = vals.shape
    k, s, w2 = acc.shape
    assert w == w2
    assert k * s < 2**31, "cell ids are int32"
    tile_k, block_n, n_blocks, n_tiles = _geometry(n, k, tile_k)
    assert k % tile_k == 0
    n_pad = n_blocks * block_n
    # the bound of the padded call: the grid ``pallas_specs`` builds
    visits, dense_steps = grid_steps(n_pad, k, tile_k)
    chunk = min(visits, CHUNK)
    _obs.gauge_set("segment_aggregate.visits", visits)
    _obs.gauge_set("segment_aggregate.dense_steps", dense_steps)

    # group the hits by cell; dead hits carry the sentinel and value 0
    dead = k * s
    live = (keys >= 0) & (keys < k) & (slots >= 0) & (slots < s)
    cells = jnp.where(live, keys * s + slots, dead).astype(jnp.int32)
    vals = jnp.where(live[:, None], vals, 0).astype(acc.dtype)
    cells, *cols = jax.lax.sort((cells, *vals.T), num_keys=1,
                                is_stable=False)
    cells = jnp.pad(cells, (0, n_pad - n), constant_values=dead)
    vals = jnp.pad(jnp.stack(cols), ((0, 0), (0, n_pad - n)))
    tile, opened = _openings(cells, tile_k * s, n_tiles, block_n)
    n_real = opened[-1]
    hit_keys = (cells // s).reshape(1, n_pad)
    hit_slots = (cells % s).reshape(1, n_pad)

    call = pl.pallas_call(
        functools.partial(_kernel, s, w, tile_k),
        **pallas_specs(n_pad, w, k, s, tile_k, block_n, acc.dtype),
        interpret=interpret,
        name="segment_aggregate",
    )

    def run_chunk(c, acc_t):
        return call(*_chunk(tile, opened, n_real, c, block_n, chunk),
                    hit_keys, hit_slots, vals, acc_t)

    acc_t = jax.lax.fori_loop(0, (n_real + chunk - 1) // chunk, run_chunk,
                              acc.transpose(1, 2, 0))
    return acc_t.transpose(2, 0, 1)
