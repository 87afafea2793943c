"""The generalized stateful operator ``O+`` (paper §4.2, Alg. 2).

``O+(WA, WS, I, f_MK, WT, S, f_mu, f_U, f_O, f_S)`` subsumes Aggregates and
Joins (Theorem 2) and admits arbitrary per-tuple key *sets* (Definition 4).

TPU adaptation of the function contract (DESIGN.md §5): the paper invokes
``f_U``/``f_O``/``f_S`` per (key, window-instance); here every user function
is *vectorized over the virtual key axis* ``K`` — the runtime hands the user
the full key-sliced state for one window slot plus an update mask, and keeps
(a) per-(key,slot) occupancy, (b) the ring of live window generations,
(c) expiry bookkeeping (``rho``, Alg. 2 L33-35) itself.  Semantics are those
of Alg. 2 processed one ready tuple at a time (``jax.lax.scan``), which the
tests pin against hand-computed traces (Appendix E).

State layout
------------
``sigma`` is a user pytree whose leaves carry leading dims ``[K, n_slots]``.
Window boundaries are global (the window grid does not depend on the key), so
one scalar ``next_l`` — the earliest non-expired window index, the paper's
``rho / WA`` — plus the ring discipline ``slot(l) = l % n_slots`` recovers
every live instance boundary.

User functions (all leaves sliced to one slot ``s``: leading dim ``[K]``):

  f_u(zeta_s, tup, win_l, mask[K])   -> (zeta_s', out_payload[K,P], out_valid[K])
  f_o(zeta_s, win_l, key_ids[K])     -> (out_payload[K,P], out_valid[K])
  f_s(zeta_s, new_left)              -> (zeta_s', occupied[K])

Defaults follow Table 1: ``f_U`` stores the tuple in a bounded per-instance
ring (``TupleStore``), ``f_O`` emits nothing, ``f_S`` purges stale tuples.
Output tuples take ``tau = right boundary`` (Observation 1) via
``prepare_out_tuples``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import tuples as T
from repro.core.windows import MULTI, SINGLE, WindowSpec

# next_l before any tuple arrived: the paper inits rho to 0 but lowers it to
# the first tuple's earliest window (Alg. 2 L24); we use a sentinel and
# resolve it on first contact so windows with negative indices work too.
UNSET_L = jnp.iinfo(jnp.int32).min


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Tup:
    """One tuple, as seen by f_U (scan-carried scalar view)."""
    tau: jax.Array       # i32[]
    payload: jax.Array   # f32[P]
    source: jax.Array    # i32[]
    keys: jax.Array      # i32[KMAX]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class OpState:
    zeta: Any            # user pytree, leaves [K, n_slots, ...]
    occupied: jax.Array  # bool[K, n_slots]  (check&Create bookkeeping)
    next_l: jax.Array    # i32[] earliest non-expired window index (= rho/WA)
    watermark: jax.Array  # i32[] instance watermark W


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Outputs:
    """Fixed-capacity output buffer for one tick (+ overflow accounting)."""
    tau: jax.Array       # i32[cap]
    payload: jax.Array   # f32[cap, P]
    valid: jax.Array     # bool[cap]
    count: jax.Array     # i32[] number of valid lanes
    overflow: jax.Array  # i32[] outputs dropped (buffer too small)

    def as_batch(self, kmax: int = 1) -> T.TupleBatch:
        return T.make_batch(self.tau, self.payload, valid=self.valid, kmax=kmax)


def _empty_outputs(cap: int, p: int) -> Outputs:
    return Outputs(tau=jnp.zeros((cap,), jnp.int32),
                   payload=jnp.zeros((cap, p), jnp.float32),
                   valid=jnp.zeros((cap,), bool),
                   count=jnp.zeros((), jnp.int32),
                   overflow=jnp.zeros((), jnp.int32))


EMIT_ROW = 128      # most rows counted together by ``_emit``'s first level


def _emit(outs: Outputs, tau: jax.Array, valid: jax.Array,
          rows: Callable[[jax.Array], jax.Array]) -> Outputs:
    """Append the rows whose ``valid`` is set, in order, to the output
    buffer (drop + count what does not fit).  ``tau`` is one time for every
    row or one per row; ``rows(idx)`` gives the payload of the rows
    ``idx``.

    Each lane of the buffer finds the row it takes in two levels: the
    valid rows are counted per group, a ``searchsorted`` on the running
    count of the groups finds the lane's group, and a count inside that
    one group finds the row.  A group holds ``rows offered // lanes``
    rows, at most ``EMIT_ROW``, so the counts inside the lanes' groups
    cost no more than one pass over ``valid``, and no running count as
    long as ``valid`` is made when the rows far outnumber the lanes (a
    window's close over a large key block)."""
    cap, n_all = outs.tau.shape[0], valid.shape[0]
    width = max(1, min(EMIT_ROW, n_all // cap))
    n_groups = -(-n_all // width)
    groups = jnp.pad(valid, (0, n_groups * width - n_all)
                     ).reshape(n_groups, width)
    ends = jnp.cumsum(jnp.sum(groups, axis=1, dtype=jnp.int32))
    n = ends[-1]
    j = jnp.arange(cap, dtype=jnp.int32) - outs.count  # new row of each lane
    take = (j >= 0) & (j < n)
    g = jnp.minimum(jnp.searchsorted(ends, j + 1, side="left"),
                    n_groups - 1)
    rank = j - jnp.where(g > 0, ends[g - 1], 0)      # among g's valid rows
    inside = jnp.cumsum(groups[g], axis=1, dtype=jnp.int32)
    idx = jnp.minimum(g * width + jnp.argmax(inside > rank[:, None],
                                             axis=1), n_all - 1)
    payload = rows(idx).astype(jnp.float32)
    tau = jnp.asarray(tau, jnp.int32)
    return Outputs(
        tau=jnp.where(take, tau[idx] if tau.ndim else tau, outs.tau),
        payload=jnp.where(take[:, None], payload, outs.payload),
        valid=outs.valid | take,
        count=jnp.minimum(outs.count + n, cap),
        overflow=outs.overflow + jnp.maximum(outs.count + n - cap, 0),
    )


# ---------------------------------------------------------------------------
# Table-1 default behaviours
# ---------------------------------------------------------------------------

def tuple_store_init(k: int, n_slots: int, ring: int, p: int):
    """Default zeta: bounded per-(key,slot) tuple ring (Table 1 f_U default)."""
    return {
        "tau": jnp.full((k, n_slots, ring), -1, jnp.int32),
        "payload": jnp.zeros((k, n_slots, ring, p), jnp.float32),
        "source": jnp.zeros((k, n_slots, ring), jnp.int32),
        "count": jnp.zeros((k, n_slots), jnp.int32),
    }


def default_f_u(zeta_s, tup: Tup, win_l, mask):
    """Store t in w.zeta of t's sender; return no phi (Table 1)."""
    ring = zeta_s["tau"].shape[-1]
    slot = jnp.mod(zeta_s["count"], ring)
    k_ids = jnp.arange(zeta_s["tau"].shape[0])
    new = {
        "tau": zeta_s["tau"].at[k_ids, slot].set(tup.tau),
        "payload": zeta_s["payload"].at[k_ids, slot].set(tup.payload),
        "source": zeta_s["source"].at[k_ids, slot].set(tup.source),
        "count": zeta_s["count"] + 1,
    }
    out = jnp.zeros((zeta_s["tau"].shape[0], tup.payload.shape[-1]), jnp.float32)
    return new, out, jnp.zeros((zeta_s["tau"].shape[0],), bool)


def default_f_o(zeta_s, win_l, key_ids):
    """Return no phi (Table 1)."""
    k = key_ids.shape[0]
    p = zeta_s["payload"].shape[-1] if isinstance(zeta_s, dict) and "payload" in zeta_s else 1
    return jnp.zeros((k, p), jnp.float32), jnp.zeros((k,), bool)


def default_f_s(ws: int):
    """Purge stale tuples (Table 1): drop entries with tau < new left bound."""
    def f_s(zeta_s, new_left):
        stale = zeta_s["tau"] < new_left
        zeta = dict(zeta_s)
        zeta["tau"] = jnp.where(stale, -1, zeta_s["tau"])
        live = jnp.sum((zeta["tau"] >= 0).astype(jnp.int32), axis=-1)
        return zeta, live > 0
    return f_s


# ---------------------------------------------------------------------------
# The operator definition
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class OperatorDef:
    """``O+(WA, WS, I, f_MK, WT, S, f_mu, f_U, f_O, f_S)`` — paper §4.2.

    ``f_mk`` may be None when the ingress already materializes key sets into
    ``TupleBatch.keys`` (our datagens do, mirroring metadata-borne keys §3).
    ``f_mu`` is not stored here: routing tables live with the *executor*
    (sn.py / vsn.py) because they are epoch state (Alg. 4), not operator
    definition.
    """
    window: WindowSpec
    n_inputs: int                                   # I
    k_virt: int                                     # virtual key space |K|
    payload_out: int                                # S (flattened width)
    init_zeta: Callable[[], Any]
    f_u: Callable = None
    f_o: Callable = None
    f_s: Callable = None
    f_mk: Optional[Callable[[T.TupleBatch], jax.Array]] = None
    out_cap: int = 256                              # per-tick output lanes
    extra_slots: int = 0                            # ring slack for batched paths
    lazy_expiry: bool = False                       # skip f_O rounds when f_O = "-"
    name: str = "o_plus"

    @property
    def slots(self) -> int:
        """Physical slot-ring size >= live window instances (slack lets the
        vectorized fast paths hold two in-flight generations per slot)."""
        return self.window.n_slots + self.extra_slots

    def slot_of(self, l):
        return jnp.mod(l, self.slots)

    def resolved(self) -> "OperatorDef":
        """Fill Table-1 defaults for unspecified functions."""
        return dataclasses.replace(
            self,
            f_u=self.f_u or default_f_u,
            f_o=self.f_o or default_f_o,
            f_s=self.f_s or default_f_s(self.window.ws),
        )

    def init_state(self) -> OpState:
        return OpState(zeta=self.init_zeta(),
                       occupied=jnp.zeros((self.k_virt, self.slots), bool),
                       next_l=jnp.full((), UNSET_L, jnp.int32),
                       watermark=jnp.zeros((), jnp.int32))


def _slice_slot(zeta, s):
    return jax.tree.map(lambda a: a[:, s], zeta)


def _set_slot(zeta, s, zeta_s):
    """Write one slot in place (an update of the slot, not a scatter)."""
    return jax.tree.map(
        lambda a, v: jax.lax.dynamic_update_index_in_dim(
            a, v.astype(a.dtype), s, 1), zeta, zeta_s)


def _expire_round(op: OperatorDef, st: OpState, outs: Outputs,
                  resp: jax.Array, key_offset,
                  occupancy: Optional[Callable] = None):
    """forwardAndShift for the earliest live window generation (Alg. 2 L12-18).

    Emits f_O for every occupied+responsible key of the expiring generation,
    then slides (WT=single) or recycles (WT=multi) the slot.  Key ids are
    ``key_offset + row``.  ``occupancy(st, s)`` gives the slot's occupied
    keys where a fast path derives them from the state, which then keeps
    no ``occupied`` mask (default: ``st.occupied[:, s]``).

    The work over all rows is one pass over the slot: f_O's validity, the
    occupancy and the count of the rows to emit.  f_O's payload is
    computed only for the rows the output buffer takes, gathered from the
    slot; f_O is pure, so it may run twice.
    """
    ws = op.window
    s = op.slot_of(st.next_l)
    zeta_s = _slice_slot(st.zeta, s)
    _, f_valid = op.f_o(zeta_s, st.next_l,
                        key_offset + jnp.arange(op.k_virt))
    occ = (st.occupied[:, s] if occupancy is None
           else occupancy(st, s))
    emit_mask = f_valid & occ & resp

    def rows(idx):
        payload, _ = op.f_o(jax.tree.map(lambda a: a[idx], zeta_s),
                            st.next_l, key_offset + idx)
        return payload

    outs = _emit(outs, ws.right_of(st.next_l), emit_mask, rows)

    if ws.wt == SINGLE:
        # slide the instance forward by WA; f_S purges / shifts state.
        zeta_new, still_occ = op.f_s(zeta_s, ws.left_of(st.next_l + 1))
        zeta = _set_slot(st.zeta, s, zeta_new)
        occupied = _set_slot(st.occupied, s, still_occ & occ)
    else:
        # recycle the slot for window generation next_l + n_slots.
        zeta = _set_slot(st.zeta, s, _slice_slot(op.init_zeta(), s))
        occupied = _set_slot(st.occupied, s, jnp.zeros_like(occ))
    if occupancy is not None:
        occupied = st.occupied        # no mask is kept beside the state
    return dataclasses.replace(st, zeta=zeta, occupied=occupied,
                               next_l=st.next_l + 1), outs


def _expire_all(op: OperatorDef, st: OpState, outs: Outputs, w,
                resp: jax.Array, key_offset=0,
                occupancy: Optional[Callable] = None):
    """while rho + WS <= W: forwardAndShift (Alg. 2 L33-35).

    NOTE the paper checks ``rho + WS < W`` with *exclusive* boundaries over
    continuous time; in integer delta ticks a window ``[l*WA, l*WA+WS)`` is
    safe to close once ``W >= l*WA + WS`` (no tuple with tau < right can
    still arrive, Definition 2), hence ``<=``.
    """
    def cond(carry):
        st, _ = carry
        return (st.next_l != UNSET_L) & (op.window.right_of(st.next_l) <= w)

    def body(carry):
        st, outs = carry
        return _expire_round(op, st, outs, resp, key_offset, occupancy)

    return jax.lax.while_loop(cond, body, (st, outs))


def process_tuple(op: OperatorDef, st: OpState, outs: Outputs, tup: Tup,
                  resp: jax.Array, valid, key_offset=0) -> Tuple[OpState, Outputs]:
    """processSN/processVSN body for one ready tuple (Alg. 2 L31-36).

    ``resp`` is the responsibility mask over virtual keys for *this*
    instance under the current epoch's f_mu (Alg. 2 L26 / Alg. 4 L23); the
    executors own its construction.

    ``key_offset`` supports the mesh owner-computes layout (vsn.shard_tick):
    a shard holding the contiguous key block ``[key_offset, key_offset +
    k_virt)`` runs the tick against its local rows while tuple keys and
    emitted key ids stay *global* — ``key_ids`` below are global values.
    """
    ws = op.window
    key_ids = key_offset + jnp.arange(op.k_virt)

    # updateW (implicit watermarks: the ready stream is sorted, §2.3).
    w = jnp.where(valid, jnp.maximum(st.watermark, tup.tau), st.watermark)
    # first contact resolves the window frontier (rho <- tau_1, Alg. 2 L24)
    next_l = jnp.where((st.next_l == UNSET_L) & valid,
                       ws.earliest_win_l(tup.tau), st.next_l)
    st = dataclasses.replace(st, watermark=w, next_l=next_l)

    # Expired windows first (Alg. 2 L33-35).  Operators whose f_O is the
    # Table-1 "-" default (e.g. ScaleJoin, which purges inside f_U) may skip
    # the round entirely — expiry then only tracks the frontier.
    if op.lazy_expiry:
        next_l = jnp.maximum(st.next_l, op.window.earliest_win_l(w))
        next_l = jnp.where(st.next_l == UNSET_L, op.window.earliest_win_l(w),
                           next_l)
        st = dataclasses.replace(st, next_l=next_l)
    else:
        st, outs = _expire_all(op, st, outs, w, resp, key_offset)

    # handleInputTuple (Alg. 2 L19-30).
    resp_tuple = resp  # bool[K] — f_mu(k) == j for this instance
    # union of one-hots over the tuple's key set, restricted to responsibility
    khit = jnp.zeros((op.k_virt,), bool)
    for kk in range(tup.keys.shape[0]):  # KMAX is small & static
        key = tup.keys[kk]
        khit = khit | ((key_ids == key) & (key >= 0))
    khit = khit & resp_tuple & valid

    l_min_raw, l_max = ws.window_indices(tup.tau)
    l_min = jnp.maximum(l_min_raw, st.next_l)  # expired generations excluded
    if ws.wt == SINGLE:
        l_max = l_min  # Alg. 2 L22: single updates only the earliest instance

    def upd_body(off, carry):
        st, outs = carry
        l = l_min + off
        active = l <= l_max
        s = op.slot_of(l)
        zeta_s = _slice_slot(st.zeta, s)
        mask = khit & active
        zeta_new, payload, f_valid = op.f_u(zeta_s, tup, l, mask)
        # check&Create + masked commit: non-selected keys keep their state.
        zeta_sel = jax.tree.map(
            lambda new, old: jnp.where(
                jnp.reshape(mask, mask.shape + (1,) * (new.ndim - 1)), new, old),
            zeta_new, zeta_s)
        zeta = _set_slot(st.zeta, s, zeta_sel)
        occupied = st.occupied.at[:, s].max(mask)
        # f_U may emit multiple outputs per key: payload [K,P] or [K,E,P].
        if payload.ndim == 3:
            emit_valid = (f_valid & mask[:, None]).reshape(-1)
            payload = payload.reshape(-1, payload.shape[-1])
        else:
            emit_valid = f_valid & mask
        outs = _emit(outs, ws.right_of(l), emit_valid,
                     lambda idx: payload[idx])
        return dataclasses.replace(st, zeta=zeta, occupied=occupied), outs

    n_upd = ws.n_slots if ws.wt == MULTI else 1
    st, outs = jax.lax.fori_loop(0, n_upd, upd_body, (st, outs))
    return st, outs


def tick(op: OperatorDef, st: OpState, ready: T.TupleBatch,
         resp: jax.Array, explicit_w=None, key_offset=0) -> Tuple[OpState, Outputs]:
    """Process one ready batch tuple-by-tuple (general, order-preserving path).

    ``explicit_w`` models *explicit watermark* propagation (§2.3): an
    end-of-tick watermark broadcast to the instance regardless of which
    tuples were routed to it — required for SN correctness when an
    instance's queue runs dry (the paper's zero-rate caveat).

    ``key_offset`` shifts the local key block to global ids for the mesh
    owner-computes layout (see ``process_tuple``); single-host executors
    leave it 0.

    Fast vectorized paths for specific operator families live in
    aggregate.py / join.py; tests pin them against this oracle.
    """
    op = op.resolved()
    outs = _empty_outputs(op.out_cap, op.payload_out)

    def body(carry, lane):
        st, outs = carry
        tup = Tup(tau=ready.tau[lane], payload=ready.payload[lane],
                  source=ready.source[lane], keys=ready.keys[lane])
        valid = ready.valid[lane] & ~ready.is_control[lane]
        st, outs = process_tuple(op, st, outs, tup, resp, valid, key_offset)
        return (st, outs), None

    (st, outs), _ = jax.lax.scan(body, (st, outs), jnp.arange(ready.batch))

    if explicit_w is not None:
        w = jnp.maximum(st.watermark, explicit_w)
        next_l = jnp.where(st.next_l == UNSET_L,
                           op.window.earliest_win_l(w), st.next_l)
        st = dataclasses.replace(st, watermark=w, next_l=next_l)
        if op.lazy_expiry:
            st = dataclasses.replace(
                st, next_l=jnp.maximum(st.next_l, op.window.earliest_win_l(w)))
        else:
            st, outs = _expire_all(op, st, outs, w, resp, key_offset)
    return st, outs
