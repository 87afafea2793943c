"""Pallas TPU kernel: ScaleGate k-way sorted merge + readiness (paper §2.4).

The synchronization-free TPU rendering of ScaleGate: given the tick's tuples
(already tagged with source ids, each source's lanes timestamp-sorted), the
kernel produces the *total order* every reader observes — a bitonic sort
network over (tau, lane) in VMEM — plus the Definition-3 watermark
``W = min_i max_m tau_i^m`` and per-lane readiness ``tau <= W``.

The network compares (tau, arrival-lane) lexicographically — the lane
tie-break rides along as the carried index — so the sort is
stable-deterministic over the full int32 tau range (no packed-key
composite, no overflow restriction).

Mosaic-ready layout (ISSUE 5): the tick lives in VMEM as a rank-2
``(rows, 128)`` tile — the lane dim is the TPU vector lane dim — and every
compare-exchange pass is a *roll*: the bitonic partner of flat lane ``p``
at stride ``s`` is ``p ^ s``, which for the lanes with bit ``s`` clear is
``p + s`` (one roll left) and for the rest ``p - s`` (one roll right).
Strides below 128 roll the lane axis, strides at/above 128 roll the
sublane axis — no rank-1 iota, no gathers, no lane-dim reshapes, which is
exactly what the Mosaic lowering path needs (``pltpu.roll`` is the native
lane rotation).  The carried triple is ``(key, lane, valid)`` so readiness
never gathers back through the permutation.

Single-program kernel, entire tick resident in VMEM (a 32K-lane tick is
128 KiB per carried array).  ``scalegate_merge`` pads any batch to the next power of two of
at least 128 lanes; padding lanes carry ``(INF_TIME, lane >= n)`` keys, so
they sort strictly after every real lane and the first ``n`` sorted
positions are exactly the unpadded order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.watermark import INF_TIME

LANES = 128                     # TPU vector lane width (last-dim tile)
# The watermark leaves the kernel as one full (8, 128) int32 tile (every
# lane holds W): Mosaic stores vectors, not scalars, to VMEM, and a full
# tile satisfies the block-shape rule with no SMEM round trip.
WMARK_TILE = (8, LANES)


def _roll(x, shift, axis):
    """Circular shift; ``pltpu.roll`` is the Mosaic-native lane rotation
    (its shift must be non-negative, so normalize mod the axis size).
    ``shift`` may be traced: the network's strides are loop variables."""
    return pltpu.roll(x, shift % x.shape[axis], axis)


def _cmp_exchange(carry, sh, axis, coord, asc):
    """One bitonic compare-exchange pass over the row-major (R, 128) tile.

    The pass pairs flat lane ``p`` with ``p ^ stride``: ``axis=1`` strides
    ``sh`` lanes (stride < 128), ``axis=0`` strides ``sh`` rows (stride =
    128 * sh); ``coord`` is the iota along ``axis`` and ``asc`` the
    per-lane ascending-block mask of the enclosing stage.  The pass is two
    rolls + selects per carried array: lanes with the stride bit clear
    read their partner ``stride`` ahead, the others ``stride`` behind.
    """
    key, idx, val = carry
    is_lo = (coord & sh) == 0

    def partner(x):
        return jnp.where(is_lo, _roll(x, -sh, axis), _roll(x, sh, axis))

    pk, pi, pv = partner(key), partner(idx), partner(val)
    # (key, idx) pairs are unique, so strict lexicographic > is total.
    lex_gt = (key > pk) | ((key == pk) & (idx > pi))
    # In an ascending block the lo lane keeps the smaller element (and the
    # hi lane the larger); descending blocks mirror.  ``take`` selects the
    # partner's element exactly when ours is on the wrong side.  (An XOR,
    # not a select between two bool vectors, which Mosaic cannot lower.)
    take = lex_gt ^ (asc != is_lo)
    return (jnp.where(take, pk, key), jnp.where(take, pi, idx),
            jnp.where(take, pv, val))


def _sort_ready(tau, valid, w):
    """The shared bitonic body: sort (tau, arrival) over the [R, 128] tile
    and gate readiness against the scalar watermark ``w`` without a gather
    (the carried key equals tau on valid lanes by construction).  Returns
    ``(order, ready)`` tiles; used by both the flat and the stacked-leaf
    kernels so their traced networks can never drift apart.

    Stages and passes are ``fori_loop``s with traced strides, so the
    compiled program holds two pass bodies whatever the tile size: an
    unrolled network grows with (passes x vregs per tile) and took
    minutes to compile at deployment tick sizes."""
    r, c = tau.shape
    vb = valid != 0
    row = jax.lax.broadcasted_iota(jnp.int32, (r, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (r, c), 1)
    lane = row * c + col
    carry = (jnp.where(vb, tau, INF_TIME), lane, valid)
    log_c = c.bit_length() - 1
    stages = (r * c).bit_length() - 1

    def stage_body(stage, carry):
        # block direction: ascending iff bit (stage+1) of the flat lane is
        # 0 — constant within each 2^(stage+1)-aligned bitonic block.
        asc = (lane & jnp.left_shift(2, stage)) == 0
        # strides 2^stage .. 128 move whole rows (sublane rolls) ...
        n_row = jnp.maximum(stage - log_c + 1, 0)
        carry = jax.lax.fori_loop(
            0, n_row, lambda i, cr: _cmp_exchange(
                cr, jnp.left_shift(1, stage - log_c - i), 0, row, asc),
            carry)
        # ... then strides 64 .. 1 move within a row (lane rolls)
        top = jnp.minimum(stage, log_c - 1)
        return jax.lax.fori_loop(
            0, top + 1, lambda i, cr: _cmp_exchange(
                cr, jnp.left_shift(1, top - i), 1, col, asc),
            carry)

    key, idx, val = jax.lax.fori_loop(0, stages, stage_body, carry)
    ready = jnp.where((val != 0) & (key <= w), 1, 0).astype(jnp.int32)
    return idx, ready


def _kernel(n_sources, tau_ref, src_ref, valid_ref,
            order_ref, ready_ref, wmark_ref):
    tau = tau_ref[...]                    # [R, 128] i32
    src = src_ref[...]                    # [R, 128] i32
    valid = valid_ref[...]                # [R, 128] i32 (0/1)
    vb = valid != 0

    # Definition 3 watermark: min over sources of (max tau per source).
    # n_sources is static and small — an unrolled scalar min-of-max chain
    # instead of a rank-1 per-source vector.
    w = None
    for s_id in range(n_sources):
        s_max = jnp.max(jnp.where((src == s_id) & vb, tau, -1))
        w = s_max if w is None else jnp.minimum(w, s_max)
    wmark_ref[...] = jnp.full(WMARK_TILE, w, jnp.int32)

    order_ref[...], ready_ref[...] = _sort_ready(tau, valid, w)


def _stacked_kernel(tau_ref, valid_ref, rep_ref,
                    order_ref, ready_ref, wmark_ref):
    """Stacked-leaf fused root merge: the watermark is not derived from the
    tuples but from the leaves' *reported* frontiers (explicit-watermark
    mode, paper §6) — ``rep_ref`` is a (1, 128) tile of per-leaf effective
    frontiers, INF on inactive/absent lanes, so ``W = min(rep)`` is the
    Definition-3 composition ``W_root = min_leaf W_leaf``."""
    w = jnp.min(rep_ref[...])
    wmark_ref[...] = jnp.full(WMARK_TILE, w, jnp.int32)
    order_ref[...], ready_ref[...] = _sort_ready(tau_ref[...],
                                                 valid_ref[...], w)


def pallas_specs(n_rows: int):
    """The call's grid/Block/out structure — shared with the lowering lint
    (kernels/lowering.py) so the linted shape can never drift from the
    executed one.  Everything is rank >= 2 with a 128 lane dim."""
    tile = (n_rows, LANES)
    return dict(
        grid=(1,),
        in_specs=[pl.BlockSpec(tile, lambda i: (0, 0)),
                  pl.BlockSpec(tile, lambda i: (0, 0)),
                  pl.BlockSpec(tile, lambda i: (0, 0))],
        out_specs=[pl.BlockSpec(tile, lambda i: (0, 0)),
                   pl.BlockSpec(tile, lambda i: (0, 0)),
                   pl.BlockSpec(WMARK_TILE, lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct(tile, jnp.int32),
                   jax.ShapeDtypeStruct(tile, jnp.int32),
                   jax.ShapeDtypeStruct(WMARK_TILE, jnp.int32)],
    )


def scalegate_merge(tau, src, valid, *, n_sources: int,
                    interpret: bool = False):
    """-> (order i32[N], ready i32[N], watermark i32[1]); any N >= 1.

    N is padded internally to the next power of two of at least 128 lanes
    and laid out as (N/128, 128); padding lanes are invalid with the
    largest arrival indices, so they sort after every real lane and
    ``order[:N]`` is exactly the unpadded (tau, arrival) total order.
    """
    n = tau.shape[0]
    n_pad = max(LANES, 1 << (n - 1).bit_length()) if n > 1 else LANES
    valid = valid.astype(jnp.int32)
    if n_pad != n:
        tau = jnp.pad(tau, (0, n_pad - n))
        src = jnp.pad(src, (0, n_pad - n))
        valid = jnp.pad(valid, (0, n_pad - n))
    rows = n_pad // LANES

    kern = functools.partial(_kernel, n_sources)
    order2, ready2, w2 = pl.pallas_call(
        kern,
        **pallas_specs(rows),
        interpret=interpret,
        name="scalegate_merge",
    )(tau.reshape(rows, LANES), src.reshape(rows, LANES),
      valid.reshape(rows, LANES))
    return (order2.reshape(n_pad)[:n], ready2.reshape(n_pad)[:n],
            w2[0, :1])


def pallas_specs_stacked(n_rows: int):
    """Grid/Block/out structure of the stacked-leaf entry — shared with its
    lowering-lint case.  Three rank-2 inputs: the (rows, 128) tau and valid
    tiles plus the (1, 128) reported-frontier tile."""
    tile = (n_rows, LANES)
    return dict(
        grid=(1,),
        in_specs=[pl.BlockSpec(tile, lambda i: (0, 0)),
                  pl.BlockSpec(tile, lambda i: (0, 0)),
                  pl.BlockSpec((1, LANES), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec(tile, lambda i: (0, 0)),
                   pl.BlockSpec(tile, lambda i: (0, 0)),
                   pl.BlockSpec(WMARK_TILE, lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct(tile, jnp.int32),
                   jax.ShapeDtypeStruct(tile, jnp.int32),
                   jax.ShapeDtypeStruct(WMARK_TILE, jnp.int32)],
    )


def scalegate_merge_stacked(tau2, src2, valid2, reports, *,
                            interpret: bool = False):
    """Fused root merge over stacked per-leaf chunk rows.

    -> (order i32[R, C] flat row-major indices, ready i32[R, C],
        watermark i32[1]); any rank-2 input, ``reports`` i32[L <= 128]
    pre-masked per-leaf effective frontiers (INF for inactive leaves).

    The [R, C] buffer is flattened row-major (arrival = flat index), padded
    to a power-of-two (rows, 128) tile like the flat kernel, and sorted by
    the same (tau, arrival) bitonic network; the watermark gate is the min
    over the reported frontiers instead of the per-source fold, so a single
    kernel call replaces the root's whole per-round merge.  ``src2`` rides
    along for signature parity with the xla oracle; the (tau, arrival)
    contract does not consult it (see core.scalegate.TIE_BREAK).
    """
    del src2
    r_in, c_in = tau2.shape
    n = r_in * c_in
    tau = tau2.reshape(n)
    valid = valid2.astype(jnp.int32).reshape(n)
    n_pad = max(LANES, 1 << (n - 1).bit_length()) if n > 1 else LANES
    if n_pad != n:
        tau = jnp.pad(tau, (0, n_pad - n))
        valid = jnp.pad(valid, (0, n_pad - n))
    n_leaves = reports.shape[0]
    assert n_leaves <= LANES, f"{n_leaves} leaves exceed one report tile"
    rep = jnp.pad(reports.astype(jnp.int32), (0, LANES - n_leaves),
                  constant_values=INF_TIME).reshape(1, LANES)
    rows = n_pad // LANES

    order2, ready2, w2 = pl.pallas_call(
        _stacked_kernel,
        **pallas_specs_stacked(rows),
        interpret=interpret,
        name="scalegate_merge_stacked",
    )(tau.reshape(rows, LANES), valid.reshape(rows, LANES), rep)
    return (order2.reshape(n_pad)[:n].reshape(r_in, c_in),
            ready2.reshape(n_pad)[:n].reshape(r_in, c_in),
            w2[0, :1])
