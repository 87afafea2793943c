"""VSN execution (paper §5, Alg. 3-4): shared Tuple Buffer, shared state.

Every instance consumes the *same* totally-ordered ready batch (the
all-gathered ScaleGate output — our shared TB), and processes exactly the
virtual keys it is responsible for under the current epoch's ``f_mu``
(Alg. 4 L23 / Alg. 2 L26).  No tuple is ever duplicated (Observation 2) and
state never moves at reconfiguration (Theorem 3): each key row of the shared
``sigma`` is written by exactly one instance per epoch, so the merged state
is simply "row k comes from instance f_mu(k)".

Two realizations:

* ``run_tick`` — single-host reference used by tests/benchmarks: ``vmap``
  over instances against the shared state, then the disjoint-writer merge.
  On one device the vmapped instances literally share memory — the paper's
  own setting.
* ``shard_tick`` / ``shard_pipeline_step`` — mesh execution: ``sigma`` rows
  are sharded over the device axis in fixed contiguous key blocks
  (owner-computes: storage layout == responsibility), the ready batch and
  the epoch tables are replicated (the replicated TB *is* the shared Tuple
  Buffer: every shard observes the identical total order), and the merge is
  a no-op by layout.  An ``f_mu`` epoch switch only swaps the replicated
  tables — no sigma row ever crosses a device (Theorem 3 made physical:
  the compiled step contains zero cross-device collectives).  Batched
  multi-tick ingest stacks T ticks and ``lax.scan``s over them inside one
  ``shard_map`` call, so the hot loop does not round-trip to Python per
  tick.  ``core.runtime.MeshPipeline`` is the driver.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

from repro.core import tuples as T
from repro.core.operator import OperatorDef, OpState, Outputs, tick


def responsibility(fmu: jax.Array, j, active: jax.Array) -> jax.Array:
    """resp[k] = (f_mu(k) == j) for an active instance, else empty."""
    return (fmu == j) & active[j]


def merge_states(stacked: OpState, fmu: jax.Array) -> OpState:
    """Disjoint-writer merge: row k of sigma comes from instance f_mu(k).

    Scalars (watermark, next_l) advance identically on all instances —
    TB delivers the same watermarks to all readers (Definition 6) — so any
    reduction that picks a consistent value works; we take the max to also
    tolerate inactive instances that skipped the tick.
    """
    def pick_rows(leaf):
        # leaf: [n_inst, K, ...] -> [K, ...]
        return leaf[fmu, jnp.arange(leaf.shape[1])]

    zeta = jax.tree.map(pick_rows, stacked.zeta)
    occupied = pick_rows(stacked.occupied)
    return OpState(zeta=zeta, occupied=occupied,
                   next_l=jnp.max(stacked.next_l),
                   watermark=jnp.max(stacked.watermark))


def merge_fast_state(stacked, fmu: jax.Array):
    """Disjoint-writer merge for the fast-path states (FastAggState /
    FastJoinState): leaves with a leading [n_inst, K, ...] key axis are
    row-picked by f_mu; global counters take max (identical on writers) and
    per-instance metrics (collisions/comparisons) sum."""
    from repro.core.aggregate import FastAggState
    from repro.core.join import FastJoinState

    if isinstance(stacked, FastAggState):
        return FastAggState(
            op_state=merge_states(stacked.op_state, fmu),
            slot_l=jnp.max(stacked.slot_l, axis=0),
            collisions=jnp.sum(stacked.collisions))
    if isinstance(stacked, FastJoinState):
        rows = jnp.arange(stacked.tau.shape[1])
        return FastJoinState(
            tau=stacked.tau[fmu, rows], pay=stacked.pay[fmu, rows],
            stream=stacked.stream[fmu, rows], n=stacked.n[fmu, rows],
            c=jnp.max(stacked.c),
            comparisons=jnp.sum(stacked.comparisons))
    raise TypeError(type(stacked))


def run_tick(op: OperatorDef, state, ready: T.TupleBatch,
             fmu: jax.Array, active: jax.Array,
             tick_fn: Callable = tick,
             merge_fn: Callable = merge_states):
    """One VSN tick over all instances against shared state.

    ``tick_fn(op, state, ready, resp) -> (state, outs)`` defaults to the
    general O+ path; the fast paths (aggregate/join) plug in with their
    matching ``merge_fn`` since they obey the same responsibility contract.
    """
    n_inst = active.shape[0]

    def per_instance(j):
        resp = responsibility(fmu, j, active)
        return tick_fn(op, state, ready, resp, explicit_w=None)

    stacked_state, stacked_outs = jax.vmap(per_instance)(jnp.arange(n_inst))
    merged = merge_fn(stacked_state, fmu)
    return merged, stacked_outs  # outputs stay per-instance (readers merge)


def pipeline_tick(sg, epoch, sigma, incoming: T.TupleBatch,
                  fmu_new: jax.Array, active_new: jax.Array,
                  tick_with_epoch: Callable, on_ready: Callable = None):
    """One full pipeline tick: ScaleGate push -> prepareReconfig -> two-phase
    epoch-split tick (Alg. 4 L17) -> advanceEpoch — the single traced body
    shared by ``VSNPipeline._step_impl``, the mesh scan (``shard_pipeline_
    step``) and the persistent K-tick drivers (``runtime.run_persistent``),
    so the per-step and batched paths can never drift apart.

    ``tick_with_epoch(sigma, ready, epoch) -> (sigma, outs)`` runs one
    phase under the epoch in effect for it; ``on_ready(ready, epoch)``
    (optional) is evaluated right after prepareReconfig — under the
    in-effect ``f_mu``, before any switch — and its result is returned as
    ``extra`` (the per-instance-load hook).

    Returns ``(sg, epoch, sigma, outs_pre, outs_post, switched, wmk,
    extra)`` where ``wmk`` is this tick's watermark report — the one
    device scalar the control lane carries back per tick.
    """
    from repro.core import elastic, scalegate

    sg, ready = scalegate.push(sg, incoming)
    epoch = elastic.prepare_reconfig(epoch, ready, fmu_new, active_new)
    pre, post = elastic.split_epoch_masks(epoch, ready)
    extra = None if on_ready is None else on_ready(ready, epoch)

    ready_pre = dataclasses.replace(
        ready, valid=pre | (ready.is_control & ready.valid))
    sigma, outs1 = tick_with_epoch(sigma, ready_pre, epoch)

    live = ready.valid & ~ready.is_control
    w_end = jnp.max(jnp.where(live, ready.tau, 0))
    epoch, switched = elastic.advance_epoch(epoch, w_end)

    ready_post = dataclasses.replace(ready, valid=post)
    sigma, outs2 = tick_with_epoch(sigma, ready_post, epoch)
    return (sg, epoch, sigma, outs1, outs2, switched, sg.wmark.value(),
            extra)


def flatten_outputs(stacked: Outputs) -> Outputs:
    """Merge per-instance output buffers into one (downstream TB ingest).

    Ordered by (tau, instance): within an instance outputs are already
    timestamp-sorted (Lemma 2), so a stable sort by tau yields the global
    order the downstream ScaleGate expects.
    """
    tau = stacked.tau.reshape(-1)
    payload = stacked.payload.reshape(-1, stacked.payload.shape[-1])
    valid = stacked.valid.reshape(-1)
    order = jnp.argsort(jnp.where(valid, tau, jnp.iinfo(jnp.int32).max),
                        stable=True)
    return Outputs(tau=tau[order], payload=payload[order], valid=valid[order],
                   count=jnp.sum(stacked.count),
                   overflow=jnp.sum(stacked.overflow))


# ---------------------------------------------------------------------------
# Mesh execution (owner-computes key blocks over a device axis)
# ---------------------------------------------------------------------------

def localize_op(op: OperatorDef, lo, rows: int) -> OperatorDef:
    """View of ``op`` over the contiguous key block ``[lo, lo + rows)``.

    ``rows`` is static (shard width); ``lo`` may be a traced shard offset.
    ``init_zeta`` leaves with a leading ``k_virt`` axis are row-sliced so the
    MULTI slot-recycle path materializes block-local fresh state.

    Contract: the operator's user functions must treat the key axis
    *positionally* — they see block-local rows and may not close over the
    global ``k_virt`` or recompute global key identity from ``arange``
    (globally-meaningful key ids arrive via the tick's ``key_offset``).
    ``scalejoin_def`` violates this (its f_U's round-robin store compares
    the global counter against local ``arange``); ScaleJoin runs on the
    mesh through ``join_local_tick`` instead, which threads
    ``k_global``/``k_offset`` through the fast path explicitly.
    """
    full_init = op.init_zeta
    k_full = op.k_virt

    def init_local():
        return jax.tree.map(
            lambda a: (jax.lax.dynamic_slice_in_dim(a, lo, rows, 0)
                       if getattr(a, "ndim", 0) and a.shape[0] == k_full
                       else a),
            full_init())

    return dataclasses.replace(op, k_virt=rows, init_zeta=init_local)


def mesh_state_spec(sigma, k_virt: int, axis: str):
    """PartitionSpec pytree for a VSN state: leaves keyed by the virtual key
    axis (leading dim ``k_virt``) shard over ``axis``; scalars/tables
    replicate.  The watermark / next_l / epoch scalars are safe to
    replicate because every shard consumes the identical replicated ready
    batch (Definition 6).  The one per-shard metric — FastJoinState's
    ``comparisons``, an [n_shards] vector in the mesh layout (see
    ``join_local_tick``) — is sharded explicitly by field, not by shape,
    so shape coincidences can never mis-shard a replicated leaf."""
    from jax.sharding import PartitionSpec as P

    from repro.core.join import FastJoinState

    def spec(a):
        nd = getattr(a, "ndim", 0)
        if nd and a.shape[0] == k_virt:
            return P(axis)
        return P()

    specs = jax.tree.map(spec, sigma)
    if isinstance(sigma, FastJoinState):
        specs = dataclasses.replace(specs, comparisons=P(axis))
    return specs


def mesh_device_put(sigma, mesh, axis: str, k_virt: int):
    """Place a freshly-initialized global state onto the mesh: key-block
    sharded sigma, replicated everything else (zero-copy resharding later —
    the layout is fixed for the pipeline's lifetime, Theorem 3)."""
    from jax.sharding import NamedSharding

    n_shards = mesh.shape[axis]
    specs = mesh_state_spec(sigma, k_virt, axis)
    return jax.tree.map(
        lambda a, sp: jax.device_put(a, NamedSharding(mesh, sp)), sigma, specs)


def general_local_tick(op: OperatorDef) -> Callable:
    """Owner-computes local tick on the general O+ path: the shard processes
    every key it stores (storage layout == responsibility; ``f_mu`` remaps
    logical work attribution, never storage)."""
    def make(lo, rows: int):
        op_l = localize_op(op, lo, rows)
        resp = jnp.ones((rows,), bool)

        def fn(state, ready):
            return tick(op_l, state, ready, resp, key_offset=lo)
        return fn
    return make


def fast_agg_local_tick(op: OperatorDef, kind: str,
                        backend: str = None) -> Callable:
    """Owner-computes local tick on the vectorized aggregate fast path.
    Ring-collision counts accumulate across the scanned ticks (per-tick
    deltas are invisible from inside one batched step)."""
    from repro.core.aggregate import tick_fast as agg_fast

    def make(lo, rows: int):
        op_l = localize_op(op, lo, rows)
        resp = jnp.ones((rows,), bool)

        def fn(state, ready):
            prev = state.collisions
            state, outs = agg_fast(op_l, kind, state, ready, resp,
                                   backend=backend, key_offset=lo)
            return dataclasses.replace(state,
                                       collisions=prev + state.collisions), outs
        return fn
    return make


def join_local_tick(window, f_j: Callable, k_virt: int, out_cap: int,
                    emit: bool = True) -> Callable:
    """Owner-computes local tick for the ScaleJoin fast path (the sliced
    layout of join.tick_fast).  ``comparisons`` becomes a per-shard
    cumulative counter of shape [1] locally / [n_shards] globally."""
    from repro.core.join import tick_fast as join_fast

    def make(lo, rows: int):
        resp = jnp.ones((rows,), bool)

        def fn(state, ready):
            prev = state.comparisons
            state, outs = join_fast(window, f_j, state, ready, resp, out_cap,
                                    emit=emit, k_global=k_virt, k_offset=lo)
            return dataclasses.replace(
                state, comparisons=prev + state.comparisons[None]), outs
        return fn
    return make


def _lift_outs(outs: Outputs) -> Outputs:
    """Expand per-tick scalar counters to [T, 1] so the shard axis can
    concatenate them (out_spec P(None, axis) -> [T, n_shards] global)."""
    return dataclasses.replace(outs, count=outs.count[..., None],
                               overflow=outs.overflow[..., None])


def _outs_spec(axis: str) -> Outputs:
    from jax.sharding import PartitionSpec as P
    return Outputs(tau=P(None, axis), payload=P(None, axis),
                   valid=P(None, axis), count=P(None, axis),
                   overflow=P(None, axis))


def shard_tick(mesh, axis: str, k_virt: int, make_local_tick: Callable,
               sigma_template):
    """Build the batched mesh VSN tick: ``step(sigma, ready_stack) ->
    (sigma, outs_stack)`` scanning T pre-gated ready batches through the
    owner-computes local tick inside ONE shard_map call.

    ``sigma`` leaves with a leading ``k_virt`` axis live sharded over
    ``axis`` in fixed contiguous key blocks; the ready stack is replicated
    (the shared TB).  No merge: rows are disjoint by layout, and the
    compiled step contains zero cross-device collectives.
    """
    from jax.sharding import PartitionSpec as P

    n_shards = mesh.shape[axis]
    assert k_virt % n_shards == 0, (k_virt, n_shards)
    rows = k_virt // n_shards
    spec_sigma = mesh_state_spec(sigma_template, k_virt, axis)

    def body(sigma, ready_stack):
        j = jax.lax.axis_index(axis)
        tick_l = make_local_tick(j * rows, rows)

        def scan_body(sigma, ready):
            sigma, outs = tick_l(sigma, ready)
            return sigma, outs

        sigma, outs = jax.lax.scan(scan_body, sigma, ready_stack)
        return sigma, _lift_outs(outs)

    def step(sigma, ready_stack):
        return jax.shard_map(body, mesh=mesh,
                         in_specs=(spec_sigma, P()),
                         out_specs=(spec_sigma, _outs_spec(axis)),
                         check_vma=False)(sigma, ready_stack)

    return step


def shard_pipeline_step(op: OperatorDef, mesh, axis: str,
                        make_local_tick: Callable, sigma_template):
    """The full VSN pipeline step on the mesh: ScaleGate merge -> epoch
    handling -> two-phase tick, scanning T stacked incoming ticks inside one
    shard_map call (batched ingest).

    Everything except sigma is replicated: the ScaleGate state, the
    watermark frontiers and the EpochState tables are identical on every
    shard by construction (each shard runs the identical merge over the
    identical replicated incoming tuples), so the paper's shared-TB contract
    holds without any communication.  Returns

        step(sg, epoch, sigma, inc_stack, fmu_new, active_new)
          -> (sg, epoch, sigma, outs_pre, outs_post, switched[T], wmark[T])

    ``wmark[T]`` is the per-tick watermark report — part of the control
    lane the persistent driver reads back (the data lane never leaves the
    device between ticks).
    """
    from jax.sharding import PartitionSpec as P

    n_shards = mesh.shape[axis]
    assert op.k_virt % n_shards == 0, (op.k_virt, n_shards)
    rows = op.k_virt // n_shards
    spec_sigma = mesh_state_spec(sigma_template, op.k_virt, axis)

    def body(sg, epoch, sigma, inc_stack, fmu_new, active_new):
        j = jax.lax.axis_index(axis)
        tick_l = make_local_tick(j * rows, rows)

        def scan_body(carry, incoming):
            sg, epoch, sigma = carry
            sg, epoch, sigma, outs1, outs2, switched, wmk, _ = pipeline_tick(
                sg, epoch, sigma, incoming, fmu_new, active_new,
                lambda s, r, e: tick_l(s, r))
            return (sg, epoch, sigma), (outs1, outs2, switched, wmk)

        (sg, epoch, sigma), (o1, o2, sw, wmk) = jax.lax.scan(
            scan_body, (sg, epoch, sigma), inc_stack)
        return sg, epoch, sigma, _lift_outs(o1), _lift_outs(o2), sw, wmk

    def step(sg, epoch, sigma, inc_stack, fmu_new, active_new):
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), spec_sigma, P(), P(), P()),
            out_specs=(P(), P(), spec_sigma, _outs_spec(axis),
                       _outs_spec(axis), P(), P()),
            check_vma=False,
        )(sg, epoch, sigma, inc_stack, fmu_new, active_new)

    return step
