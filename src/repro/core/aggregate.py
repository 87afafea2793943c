"""Aggregates: ``A`` and the multi-key ``A+`` (paper §2.1, §4, Appendix D).

``A(WA, WS, 1, f_SK, WT, S, f_A, f_R)`` is instantiated on ``O+`` per
Theorem 2 (I=1, ``f_A -> f_O``, ``f_R -> f_S``/``f_U``).  ``A+`` replaces
``f_SK`` with ``f_MK`` (Definition 5) — in our runtime that is simply
``KMAX > 1`` key sets in the tuple batch, so A and A+ share code; this *is*
the paper's point that O+ unifies them.

Shipped instances (Appendix D):
  * ``count_aggregate``     — Operator 4/5: wordcount / paircount counters.
  * ``longest_aggregate``   — Operator 1/2: longest tweet per hashtag
                              (the §1 running example, traced in Appendix E).
  * ``reduce_aggregate``    — generic commutative-monoid f_R.

``tick_fast`` is the TPU fast path for commutative reducers: the whole ready
batch is scattered into (key, window-slot) cells at once instead of scanning
tuple-by-tuple — valid because the reducer is commutative and because a ready
tuple can never land in a window its own timestamp has expired (Lemma 1
argument, DESIGN.md §5).  Slot-ring slack (``extra_slots``) absorbs the
window generations spanned by one tick; an overrun is *counted*, never
silent.  ``tests/test_aggregate.py`` pins tick_fast == tick (general path).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from repro.core import tuples as T
from repro.core.operator import (UNSET_L, OperatorDef, OpState, Outputs,
                                 _emit, _empty_outputs, _expire_all)
from repro.core.windows import MULTI, SINGLE, WindowSpec


def reduce_aggregate(window: WindowSpec, k_virt: int, *, width: int = 1,
                     f_r: Callable, init_val: float, emit_key: bool = True,
                     out_cap: int = 256, extra_slots: int = 0,
                     n_inputs: int = 1,
                     name: str = "aggregate") -> OperatorDef:
    """A/A+ with an incremental reducer f_R and expiry output f_A.

    zeta: {"acc": f32[K, slots, width]}; f_O emits ``[key, acc...]``.
    Output payloads are float32, whose integers are exact only up to
    2^24: over a larger key space f_O emits ``[key - lo, acc..., lo]``
    with ``lo = key % 256``, each part exact, and the key is their sum.
    """
    split = emit_key and k_virt > 2**24
    p_out = width + int(emit_key) + int(split)

    def init_zeta():
        slots = window.n_slots + extra_slots
        return {"acc": jnp.full((k_virt, slots, width), init_val, jnp.float32)}

    def f_u(zeta_s, tup, win_l, mask):
        acc = f_r(zeta_s["acc"], tup.payload)          # [K, width]
        k = zeta_s["acc"].shape[0]
        return ({"acc": acc},
                jnp.zeros((k, p_out), jnp.float32),
                jnp.zeros((k,), bool))

    def f_o(zeta_s, win_l, key_ids):
        payload = zeta_s["acc"]
        if emit_key:
            lo = key_ids % 256 if split else jnp.zeros_like(key_ids)
            payload = jnp.concatenate(
                [(key_ids - lo)[:, None].astype(jnp.float32), payload]
                + ([lo[:, None].astype(jnp.float32)] if split else []),
                axis=-1)
        return payload, jnp.ones((key_ids.shape[0],), bool)

    def f_s(zeta_s, new_left):
        k = zeta_s["acc"].shape[0]
        return ({"acc": jnp.full_like(zeta_s["acc"], init_val)},
                jnp.zeros((k,), bool))

    return OperatorDef(window=window, n_inputs=n_inputs, k_virt=k_virt,
                       payload_out=p_out,
                       init_zeta=init_zeta, f_u=f_u, f_o=f_o, f_s=f_s,
                       out_cap=out_cap, extra_slots=extra_slots, name=name)


def count_aggregate(window: WindowSpec, k_virt: int, **kw) -> OperatorDef:
    """Operator 4/5: per-key tuple count (wordcount / paircount)."""
    return reduce_aggregate(window, k_virt, width=1,
                            f_r=lambda acc, payload: acc + 1.0,
                            init_val=0.0, name=kw.pop("name", "count"), **kw)


def longest_aggregate(window: WindowSpec, k_virt: int, **kw) -> OperatorDef:
    """Operator 1/2: longest tweet per hashtag — payload[0] = length(phi)."""
    return reduce_aggregate(window, k_virt, width=1,
                            f_r=lambda acc, payload: jnp.maximum(acc, payload[..., :1]),
                            init_val=0.0, name=kw.pop("name", "longest"), **kw)


# ---------------------------------------------------------------------------
# Vectorized fast path (commutative reducers)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FastAggState:
    op_state: OpState
    slot_l: jax.Array      # i32[slots] window generation currently in each slot
    collisions: jax.Array  # i32[] ring overruns in the LAST tick (delta)


def fast_init(op: OperatorDef) -> FastAggState:
    return FastAggState(op_state=op.init_state(),
                        slot_l=jnp.arange(op.slots, dtype=jnp.int32),
                        collisions=jnp.zeros((), jnp.int32))


def _segment_tile_k(k: int) -> int:
    """Largest MXU-friendly tile that divides K (the kernel asserts K % tile).

    The hit-block axis needs no shim here: ``segment_aggregate`` pads N
    to whole hit blocks internally (dead -1 keys, zero values), so the
    concatenated (slot-generation x key-column) hit vectors below can have
    any length on any backend."""
    return 128 if k % 128 == 0 else k


def _scatter_reduce(op: OperatorDef, kind: str, acc, ready: T.TupleBatch,
                    resp: jax.Array, next_l, backend: str = None,
                    key_offset=0):
    """Scatter the whole tick into (key, slot) cells: the paper's per-tuple
    f_R loop becomes one segment-reduce, executed by the dispatched
    ``segment_aggregate`` kernel for additive reducers (count/sum; ``xla``
    resolves to the jnp scatter-add oracle, the Pallas backends to the
    one-hot matmul kernel).  ``max`` is not additive and keeps the scatter.

    ``f_MK`` returns a key *set* (Definition 4): a key repeated inside one
    tuple's KMAX-padded key array contributes exactly once, matching the
    general path's union of one-hots — earlier-column duplicates are masked.

    ``key_offset`` maps global tuple keys into the local row block
    ``[key_offset, key_offset + k_virt)`` (mesh owner-computes layout);
    out-of-block keys are dropped like NO_KEY.  Returns the extra mask
    ``m_any`` (key hits irrespective of ``resp``) used for bookkeeping that
    must stay identical across instances/shards (slot_l).
    """
    ws = op.window
    live = ready.valid & ~ready.is_control
    l_min = jnp.maximum(ws.earliest_win_l(ready.tau), next_l)
    l_max = ws.latest_win_l(ready.tau)
    if ws.wt == SINGLE:
        l_max = l_min
    dup_cols = []   # per kk: same key already seen in an earlier column
    for kk in range(ready.kmax):
        dup = jnp.zeros((ready.batch,), bool)
        for kk2 in range(kk):
            dup = dup | (ready.keys[:, kk2] == ready.keys[:, kk])
        dup_cols.append(dup)
    hits_l = []
    hits_k = []
    hits_m = []
    hits_any = []
    for d in range(ws.n_slots if ws.wt == MULTI else 1):
        l = l_min + d
        in_range = (l <= l_max) & live
        for kk in range(ready.kmax):
            key = ready.keys[:, kk] - key_offset
            in_block = (ready.keys[:, kk] >= 0) & (key >= 0) & \
                (key < op.k_virt) & ~dup_cols[kk]
            k_safe = jnp.clip(key, 0, op.k_virt - 1)
            hits_l.append(l)
            hits_k.append(k_safe)
            hits_m.append(in_range & in_block & resp[k_safe])
            # slot-grid bookkeeping mask: a live tuple marks its window
            # generations regardless of key/resp/block, so the value is
            # identical on every instance and every mesh shard.
            hits_any.append(in_range)
    l = jnp.concatenate(hits_l)
    k = jnp.concatenate(hits_k)
    m = jnp.concatenate(hits_m)
    m_any = jnp.concatenate(hits_any)
    s = op.slot_of(l)
    if kind == "max":
        val = jnp.tile(ready.payload[:, :1], (l.shape[0] // ready.batch, 1))
        acc = acc.at[k, s].max(jnp.where(m[:, None], val, -jnp.inf), mode="drop")
    else:
        from repro.kernels.segment_aggregate.ops import segment_aggregate_op
        if kind == "count":
            val = jnp.ones((l.shape[0], 1), jnp.float32)
        else:  # "sum"
            val = jnp.tile(ready.payload[:, :acc.shape[-1]],
                           (l.shape[0] // ready.batch, 1))
        acc = segment_aggregate_op(
            jnp.where(m, k, -1), s, jnp.where(m[:, None], val, 0.0), acc,
            tile_k=_segment_tile_k(acc.shape[0]), backend=backend)
    return acc, k, s, l, m, m_any


def tick_fast(op: OperatorDef, kind: str, st: FastAggState,
              ready: T.TupleBatch, resp: jax.Array, *,
              backend: str = None,
              key_offset=0) -> Tuple[FastAggState, Outputs]:
    """Whole-tick scatter update, then expiry (order-free for commutative f_R).

    ``key_offset`` runs the tick on a local key block (mesh layout, see
    ``_scatter_reduce``); emitted key ids stay global.
    """
    op = op.resolved()
    ops = st.op_state
    live = ready.valid & ~ready.is_control
    any_live = jnp.any(live)
    w_end = jnp.maximum(ops.watermark,
                        jnp.max(jnp.where(live, ready.tau, 0)))
    # first contact resolves the window frontier (cf. operator.process_tuple)
    first_tau = jnp.min(jnp.where(live, ready.tau, jnp.iinfo(jnp.int32).max))
    next_l = jnp.where((ops.next_l == UNSET_L) & any_live,
                       op.window.earliest_win_l(first_tau), ops.next_l)
    ops = dataclasses.replace(ops, next_l=next_l)

    acc, k_idx, s_idx, l_idx, m_idx, m_any = _scatter_reduce(
        op, kind, ops.zeta["acc"], ready, resp, ops.next_l, backend,
        key_offset)

    # Ring-overrun detection: the live window generations spanned by this
    # tick must fit the physical slot ring, else two generations alias one
    # slot (the counted-not-silent contract; pick extra_slots >= tick
    # tau-span / WA to stay clean).
    latest = jnp.max(jnp.where(live, op.window.latest_win_l(ready.tau),
                               ops.next_l))
    span = latest - ops.next_l + 1
    coll = jnp.maximum(span - op.slots, 0) * any_live.astype(jnp.int32)
    if kind == "count":
        # a count cell is occupied exactly when its count is positive:
        # each live hit adds one, and a window's close resets its slot to
        # zero.  So the close reads occupancy off the counts, and no
        # per-tick scatter of the hits into a (key, slot) mask is needed.
        occ = ops.occupied

        def occupancy(st_, s):
            return jnp.any(st_.zeta["acc"][:, s] > 0, -1)
    else:
        occ = ops.occupied.at[k_idx, s_idx].max(m_idx, mode="drop")
        occupancy = None
    # slot_l tracks which window generation owns each ring slot — a global
    # property of the window grid, so the update mask ignores keys, resp
    # and the local block entirely (m_any = lane-in-range only): every
    # instance/shard computes the identical value (replication-safe on the
    # mesh, and the disjoint-writer max-merge is unchanged on one host).
    slot_l = st.slot_l.at[s_idx].set(jnp.where(m_any, l_idx, st.slot_l[s_idx]),
                                     mode="drop")

    ops = dataclasses.replace(ops, zeta={"acc": acc}, occupied=occ,
                              watermark=w_end)
    outs = _empty_outputs(op.out_cap, op.payload_out)
    ops, outs = _expire_all(op, ops, outs, w_end, resp, key_offset,
                            occupancy)
    return (FastAggState(op_state=ops, slot_l=slot_l,
                         collisions=coll), outs)
