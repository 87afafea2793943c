"""Pallas TPU kernel: ScaleJoin blocked window band-join (paper Q3-Q6 hot loop).

Intra-chip VSN, literally (DESIGN.md §2): the incoming tuple block lives
once in HBM and is read by *every* grid program — the shared Tuple Buffer.
Each program owns a tile of virtual-key rows of the stored-tuple ring (its
``f_mu`` share, via the BlockSpec index map) and compares the whole incoming
block against its tile: no tuple duplication, disjoint state, deterministic.

Mosaic layout: the stored ring enters transposed, ring slot on sublanes
and keys on lanes (``[R, K]``, payload ``[P, R, K]``), so a tile is a
stack of full ``(R, TK)`` vreg tiles; the incoming block enters
lane-replicated, ``(B, TK)`` per field (payload ``[P, B, TK]``), B padded
to the sublane quantum with tau = INF_TIME rows (past every freshness
horizon: they match nothing and count no comparisons, the
``band_join_counts`` neutral element).  The kernel walks the incoming
tuples with a ``fori_loop``: one ``(1, TK)`` row of each field broadcasts
over the ring's sublanes (Mosaic broadcasts along one axis at a time), and
the match mask is reduced over the ring into a lane-major ``(1, TK)`` row
of ``counts``.  No iota, no rank > 3 values, no gathers.

Shapes
  new_tau  i32[B]            incoming event times (timestamp-sorted tick)
  new_src  i32[B]            stream ids (0 = L, 1 = R)
  new_pay  f32[B, P]         payloads
  st_tau   i32[K, R]         stored ring event times (-1 = empty)
  st_src   i32[K, R]
  st_pay   f32[K, R, P]
outputs
  counts   i32[B, K]         matches of incoming b against key row k
  comps    i32[K_tiles, 1]   live comparisons per tile (roofline accounting;
                             the kernel writes each tile's count as a full
                             (8, 128) block — Mosaic's block rule — and the
                             wrapper keeps one lane)

Band predicate (the [13]/[21] benchmark): matches iff
``|newL.phi[a] - newR.phi[a]| <= band`` for a < n_attrs, with stream and
``tau_new - tau_stored <= WS`` freshness (purge-on-read).

Tiling: grid over K tiles; per step the program holds the (2 + P) incoming
(B, TK) blocks plus (R, TK) + (P, R, TK) ring tiles in VMEM.  With B=256,
TK=128, R=64, P=4 (f32): 768 KB of incoming rows + 192 KB of ring tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.watermark import INF_TIME

SUBLANES = 8                    # f32 sublane quantum for the incoming block
COMPS_TILE = (SUBLANES, 128)    # one full vreg tile per program's count


def _kernel(ws, band, n_attrs,
            new_tau_ref, new_src_ref, new_pay_ref,
            st_tau_ref, st_src_ref, st_pay_ref,
            counts_ref, comps_ref):
    st_tau = st_tau_ref[...]              # [R, TK]
    st_src = st_src_ref[...]              # [R, TK]
    st_pay = [st_pay_ref[a] for a in range(n_attrs)]   # n_attrs x [R, TK]
    live = st_tau >= 0

    def body(b, comps):
        row = (pl.ds(b, 1), slice(None))
        # freshness + stream predicates of incoming tuple b: [R, TK]
        opp = (live & (st_tau + ws >= new_tau_ref[row])
               & (st_src != new_src_ref[row]))
        # band predicate on the first n_attrs payload attributes
        hit = opp
        for a in range(n_attrs):
            hit = hit & (jnp.abs(new_pay_ref[(a,) + row] - st_pay[a]) <= band)
        counts_ref[row] = jnp.sum(hit.astype(jnp.int32), axis=0,
                                  keepdims=True)
        return comps + jnp.sum(opp.astype(jnp.int32), axis=0, keepdims=True)

    comps = jax.lax.fori_loop(0, counts_ref.shape[0], body,
                              jnp.zeros((1, st_tau.shape[1]), jnp.int32))
    comps_ref[...] = jnp.full((1,) + COMPS_TILE, jnp.sum(comps), jnp.int32)


def pallas_specs(b: int, p: int, k: int, r: int, tile_k: int):
    """Grid/Block/out structure, shared with the lowering lint.  The
    incoming block is broadcast to every program; the stored-ring tiles
    walk the key (lane) axis.  All specs rank >= 2."""
    grid = (k // tile_k,)
    return dict(
        grid=grid,
        in_specs=[
            # the shared tuple block: every program maps the same HBM block
            pl.BlockSpec((b, tile_k), lambda i: (0, 0)),
            pl.BlockSpec((b, tile_k), lambda i: (0, 0)),
            pl.BlockSpec((p, b, tile_k), lambda i: (0, 0, 0)),
            # the program's key-column tile (its f_mu share)
            pl.BlockSpec((r, tile_k), lambda i: (0, i)),
            pl.BlockSpec((r, tile_k), lambda i: (0, i)),
            pl.BlockSpec((p, r, tile_k), lambda i: (0, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((b, tile_k), lambda i: (0, i)),
            pl.BlockSpec((1,) + COMPS_TILE, lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.int32),
            jax.ShapeDtypeStruct((grid[0],) + COMPS_TILE, jnp.int32),
        ],
    )


def window_join(new_tau, new_src, new_pay, st_tau, st_src, st_pay, *,
                ws: int, band: float = 10.0, n_attrs: int = 2,
                tile_k: int = 128, interpret: bool = False):
    b, p = new_pay.shape
    k, r = st_tau.shape
    tile_k = min(tile_k, k)
    assert k % tile_k == 0

    # sublane-align the incoming block: tau = INF_TIME padding lanes fail
    # every freshness test, so counts rows past b are sliced off and comps
    # is untouched.
    b_pad = -(-b // SUBLANES) * SUBLANES
    if b_pad != b:
        new_tau = jnp.pad(new_tau, (0, b_pad - b), constant_values=INF_TIME)
        new_src = jnp.pad(new_src, (0, b_pad - b))
        new_pay = jnp.pad(new_pay, ((0, b_pad - b), (0, 0)))

    def lanes(x):                       # replicate a [..., B] field
        return jnp.broadcast_to(x[..., None], x.shape + (tile_k,))

    kern = functools.partial(_kernel, ws, band, n_attrs)
    counts, comps = pl.pallas_call(
        kern,
        **pallas_specs(b_pad, p, k, r, tile_k),
        interpret=interpret,
        name="window_join",
    )(lanes(new_tau), lanes(new_src), lanes(new_pay.T), st_tau.T, st_src.T,
      st_pay.transpose(2, 1, 0))
    return counts[:b], comps[:, 0, :1]
