"""Joins: ``J``/``J+`` and ScaleJoin (paper §2.1, §4, Appendix D Operator 3).

``J(WA, WS, 2, f_SK, WT, S, f_J)`` matches pairs of tuples, one per input
stream, falling in same-boundary window instances of the same key
(Definition 1).  ScaleJoin is the ``J+`` used throughout the evaluation
(Q3-Q6): ``f_MK`` returns *all* ``K`` virtual keys, every instance counts
every tuple, each tuple is *stored* round-robin under exactly one key
(``c % K``), and each instance compares incoming tuples against the tuples
stored under its keys — disjoint-parallel and skew-resilient.

Two execution paths:
  * the general ``operator.tick`` scan path (Operator 3 transcribed into the
    vectorized f_U contract) — the semantic oracle;
  * ``tick_fast`` — blocked whole-tick compare: incoming-block x stored-ring
    plus the in-block cross-stream upper triangle, exactly once per pair.
    ``kernels/window_join`` is its Pallas twin for the intra-chip domain.

``f_J`` is a vectorized predicate ``f(payload_L[..., PL], payload_R[..., PR])
-> bool[...]``; ``band_predicate`` builds the Q3 benchmark predicate and
``hedge_predicate`` the Q6 NYSE one.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from repro.core import tuples as T
from repro.core.operator import (OperatorDef, Outputs, Tup, _emit,
                                 _empty_outputs)
from repro.core.windows import SINGLE, WindowSpec


def band_predicate(width: float = 10.0, attrs: int = 2) -> Callable:
    """Q3 predicate: |phi_L[i] - phi_R[i]| <= width for the first ``attrs``."""
    def f_j(pl, pr):
        d = jnp.abs(pl[..., :attrs] - pr[..., :attrs])
        return jnp.all(d <= width, axis=-1)
    return f_j


def hedge_predicate(lo: float = -1.05, hi: float = -0.95) -> Callable:
    """Q6 NYSE predicate on payload ``[id, nd]`` (nd precomputed at ingress):
    different company and ND_R / ND_L in [lo, hi] (negative correlation)."""
    def f_j(pl, pr):
        ratio = pr[..., 1] / jnp.where(pl[..., 1] == 0, 1e-9, pl[..., 1])
        return (pl[..., 0] != pr[..., 0]) & (ratio >= lo) & (ratio <= hi)
    return f_j


def _directed(f_j, pay_new, src_new, pay_stored):
    """Apply f_J with stream-consistent argument order (L first)."""
    lr = f_j(pay_new, pay_stored)   # new is L, stored is R
    rl = f_j(pay_stored, pay_new)   # stored is L, new is R
    return jnp.where(src_new == 0, lr, rl)


def scalejoin_def(window: WindowSpec, k_virt: int, f_j: Callable, *,
                  payload_width: int, ring: int, out_cap: int = 256,
                  name: str = "scalejoin") -> OperatorDef:
    """Operator 3 on the general O+ path (WT=single, WA=delta, I=2).

    zeta per key: tuple ring (tau/payload/stream), per-key store cursor n,
    and the global round-robin counter c (replicated per key — every key
    counts every tuple, Operator 3 L10-11).
    """
    if window.wt != SINGLE:
        raise ValueError("ScaleJoin uses WT=single")

    def init_zeta():
        return {
            "tau": jnp.full((k_virt, 1, ring), -1, jnp.int32),
            "pay": jnp.zeros((k_virt, 1, ring, payload_width), jnp.float32),
            "stream": jnp.zeros((k_virt, 1, ring), jnp.int32),
            "n": jnp.zeros((k_virt, 1), jnp.int32),     # per-key store cursor
            "c": jnp.zeros((k_virt, 1), jnp.int32),     # global tuple counter
        }

    def f_u(zeta_s, tup: Tup, win_l, mask):
        # zeta_s leaves are slot-sliced: tau/pay/stream [K, ring(,P)], n/c [K]
        k = zeta_s["tau"].shape[0]
        key_ids = jnp.arange(k)
        # purge stale opposite tuples (Operator 3 L18-19)
        fresh = zeta_s["tau"] + window.ws >= tup.tau
        live = (zeta_s["tau"] >= 0) & fresh
        tau = jnp.where(live, zeta_s["tau"], -1)
        # match against opposite-stream stored tuples (L20-21)
        opp = live & (zeta_s["stream"] != tup.source)
        hit = opp & _directed(f_j, tup.payload, tup.source, zeta_s["pay"])
        out_pay = jnp.concatenate([
            jnp.broadcast_to(tup.payload, (k, ring, tup.payload.shape[-1])),
            zeta_s["pay"]], axis=-1)
        # store round-robin: the key with c % K == k stores t (L22-23)
        store = (jnp.mod(zeta_s["c"], k_virt) == key_ids)
        pos = jnp.mod(zeta_s["n"], ring)
        new = {
            "tau": tau.at[key_ids, pos].set(
                jnp.where(store, tup.tau, tau[key_ids, pos])),
            "pay": zeta_s["pay"].at[key_ids, pos].set(
                jnp.where(store[:, None], tup.payload,
                          zeta_s["pay"][key_ids, pos])),
            "stream": zeta_s["stream"].at[key_ids, pos].set(
                jnp.where(store, tup.source, zeta_s["stream"][key_ids, pos])),
            "n": zeta_s["n"] + store.astype(jnp.int32),
            "c": zeta_s["c"] + 1,
        }
        return new, out_pay, hit

    return OperatorDef(window=window, n_inputs=2, k_virt=k_virt,
                       payload_out=2 * payload_width, init_zeta=init_zeta,
                       f_u=f_u, f_o=None, f_s=None, out_cap=out_cap,
                       lazy_expiry=True, name=name)


def band_join_counts(st: "FastJoinState", ready: T.TupleBatch,
                     window: WindowSpec, *, band: float = 10.0,
                     n_attrs: int = 2, backend: str = None):
    """Counting-only band-join tick via the dispatched ``window_join`` kernel.

    The Pallas twin of ``tick_fast`` phase 1 under full responsibility
    (every key row live): per-incoming-tuple match counts against the stored
    rings plus the live-comparison total — the Q3/Q6 throughput accounting
    path, with the backend (``xla`` ref oracle on CPU, Pallas on TPU) picked
    by the kernel dispatcher.  Returns ``(counts i32[B, K], comparisons)``.

    The kernel has no validity input, so invalid/control lanes (the padding
    of a static ScaleGate batch) are neutralized by pushing their tau past
    every stored tuple's freshness horizon — they match nothing and count
    no comparisons, same as ``tick_fast``'s ``live_in`` mask.  The kernel
    applies the identical trick to sublane-align the incoming block (B is
    padded to a multiple of 8 with INF_TIME lanes), so any ready-batch
    size dispatches cleanly on every backend.
    """
    from repro.core.watermark import INF_TIME
    from repro.kernels.window_join.ops import window_join_op

    live = ready.valid & ~ready.is_control
    tau = jnp.where(live, ready.tau, INF_TIME)
    return window_join_op(tau, ready.source, ready.payload,
                          st.tau, st.stream, st.pay, ws=window.ws,
                          band=band, n_attrs=n_attrs, backend=backend)


# ---------------------------------------------------------------------------
# Blocked fast path (the TPU execution; kernels/window_join is its twin)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FastJoinState:
    tau: jax.Array      # i32[K, R] stored event times (-1 = empty)
    pay: jax.Array      # f32[K, R, P]
    stream: jax.Array   # i32[K, R]
    n: jax.Array        # i32[K] per-key store cursor
    c: jax.Array        # i32[] global round-robin tuple counter
    comparisons: jax.Array  # f32[] comparisons in the LAST tick (per-tick
    #                         delta: cumulative sums break under the
    #                         per-instance vmap + disjoint-writer merge)


def fast_join_init(k_virt: int, ring: int, payload_width: int) -> FastJoinState:
    return FastJoinState(
        tau=jnp.full((k_virt, ring), -1, jnp.int32),
        pay=jnp.zeros((k_virt, ring, payload_width), jnp.float32),
        stream=jnp.zeros((k_virt, ring), jnp.int32),
        n=jnp.zeros((k_virt,), jnp.int32),
        c=jnp.zeros((), jnp.int32),
        comparisons=jnp.zeros((), jnp.float32),
    )


def tick_fast(window: WindowSpec, f_j: Callable, st: FastJoinState,
              ready: T.TupleBatch, resp: jax.Array, out_cap: int,
              emit: bool = True, k_global: int = None,
              k_offset=0) -> Tuple[FastJoinState, Outputs]:
    """Whole-tick ScaleJoin: block-compare + in-block triangle + scatter store.

    Two layouts:
      * monolithic (default): ``st`` holds all K_virt rows, ``resp`` masks
        this instance's responsibility (reference executor).
      * sliced (``k_global``/``k_offset`` set): ``st`` holds only this
        instance's contiguous row block — the owner-computes layout of
        vsn.shard_tick, where work partitions perfectly (each pair compared
        by exactly one instance, zero duplicated compute).

    Requires ``ready.batch <= k_global`` (one store row per tuple per tick).
    """
    k_virt, ring = st.tau.shape
    kg = k_global if k_global is not None else k_virt
    b = ready.batch
    p = ready.payload.shape[-1]
    assert b <= kg, "fast path stores at most one tuple per key per tick"
    live_in = ready.valid & ~ready.is_control

    rank = jnp.cumsum(live_in.astype(jnp.int32)) - live_in.astype(jnp.int32)
    store_key_g = jnp.mod(st.c + rank, kg)             # global key ids
    in_slice = (store_key_g >= k_offset) & (store_key_g < k_offset + k_virt)
    store_key = jnp.clip(store_key_g - k_offset, 0, k_virt - 1)

    # --- phase 1: incoming block vs stored rings (resp rows only) ---------
    fresh = (st.tau[None] + window.ws >= ready.tau[:, None, None])
    stored_live = (st.tau[None] >= 0) & fresh          # [B, K, R]
    opp = stored_live & (st.stream[None] != ready.source[:, None, None])
    pred = _directed(f_j, ready.payload[:, None, None, :],
                     ready.source[:, None, None], st.pay[None])
    hit1 = opp & pred & resp[None, :, None] & live_in[:, None, None]
    comps1 = jnp.sum((opp & resp[None, :, None] &
                      live_in[:, None, None]).astype(jnp.float32))

    # --- phase 2: in-block cross-stream upper triangle ---------------------
    ii = jnp.arange(b)
    earlier = ii[None, :] < ii[:, None]                # j earlier than i
    cross = ready.source[:, None] != ready.source[None, :]
    within = ready.tau[:, None] - ready.tau[None, :] <= window.ws
    pred2 = _directed(f_j, ready.payload[:, None, :],
                      ready.source[:, None], ready.payload[None])
    owner = resp[store_key] & in_slice                 # owner of earlier tuple
    hit2 = (earlier & cross & within & pred2 & owner[None, :] &
            live_in[:, None] & live_in[None, :])
    comps2 = jnp.sum((earlier & cross & owner[None, :] & live_in[:, None] &
                      live_in[None, :]).astype(jnp.float32))

    # --- outputs ------------------------------------------------------------
    outs = _empty_outputs(out_cap, 2 * p)
    if emit:
        # Observation 1: output tau = right boundary = incoming tau + WA.
        pay1 = jnp.concatenate(
            [jnp.broadcast_to(ready.payload[:, None, None, :],
                              (b, k_virt, ring, p)),
             jnp.broadcast_to(st.pay[None], (b, k_virt, ring, p))], axis=-1)
        tau1 = jnp.broadcast_to((ready.tau + window.wa)[:, None, None],
                                (b, k_virt, ring))
        outs = _emit(outs, tau1.reshape(-1), hit1.reshape(-1),
                     lambda idx: pay1.reshape(-1, 2 * p)[idx])
        pay2 = jnp.concatenate(
            [jnp.broadcast_to(ready.payload[:, None, :], (b, b, p)),
             jnp.broadcast_to(ready.payload[None], (b, b, p))], axis=-1)
        tau2 = jnp.broadcast_to((ready.tau + window.wa)[:, None], (b, b))
        outs = _emit(outs, tau2.reshape(-1), hit2.reshape(-1),
                     lambda idx: pay2.reshape(-1, 2 * p)[idx])

    # --- phase 3: store (round-robin, one key per tuple) -------------------
    pos = jnp.mod(st.n[store_key] + 0, ring)
    row = jnp.where(live_in & in_slice, store_key, k_virt)  # drop others
    st = FastJoinState(
        tau=st.tau.at[row, pos].set(ready.tau, mode="drop"),
        pay=st.pay.at[row, pos].set(ready.payload, mode="drop"),
        stream=st.stream.at[row, pos].set(ready.source, mode="drop"),
        n=st.n.at[row].add(1, mode="drop"),
        c=st.c + jnp.sum(live_in.astype(jnp.int32)),
        comparisons=comps1 + comps2,
    )
    return st, outs
