"""ScaleGate / Elastic ScaleGate as a batched JAX merge (paper §2.4, §6).

The original ScaleGate is a lock-free skip list merging timestamp-sorted
source streams into one totally-ordered stream of *ready* tuples
(Definition 3), delivered to every reader.  On an SPMD TPU the
synchronization problem dissolves — the total order is a property of the
merged batch itself, which every instance observes identically (DESIGN.md
§2).  What we keep is ScaleGate's *semantics*, as a pure function:

    push(state, incoming) -> (state', ready_batch)

* tuples from each source arrive timestamp-sorted;
* the watermark is ``W = min_i max_m tau_i^m`` over active sources;
* the ready batch is totally ordered by ``(tau, source, arrival)`` and
  contains exactly the tuples with ``tau <= W`` not yet delivered;
* non-ready tuples wait in a fixed-capacity stash (TPU state is static —
  overflow is counted and surfaced, never silent).

The Elastic ScaleGate (ESG) extensions map to:
* ``addSources``/``removeSources``  -> watermark frontier add/flush
  (Lemma 3 safe lower bound / "flush tuple" of §6);
* ``addReaders``/``removeReaders``  -> the *reader* set is the executor's
  active-instance mask — every reader sees the same ready batch by
  construction, so reader membership is handled downstream (vsn.py).

``repro/kernels/scalegate_merge`` is the Pallas realization of the same
merge for the intra-chip (true shared-memory) domain.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import tuples as T
from repro.core import watermark as wm


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ScaleGateState:
    stash: T.TupleBatch          # fixed-capacity not-yet-ready tuples
    wmark: wm.WatermarkState     # per-source frontiers (Definition 3)
    overflow: jax.Array          # i32 count of tuples dropped on stash overflow

    @property
    def capacity(self) -> int:
        return self.stash.batch


def init_scalegate(n_sources: int, capacity: int, kmax: int,
                   payload_width: int, active=None) -> ScaleGateState:
    """``active`` masks the initial ESG source set: a hierarchical leaf gate
    (repro.ingest.leaf) owns only a subset of the global source ids and must
    not let the others gate its watermark."""
    return ScaleGateState(
        stash=T.empty_batch(capacity, kmax, payload_width),
        wmark=wm.init_watermark(n_sources, active=active),
        overflow=jnp.zeros((), jnp.int32),
    )


def _stable_order(tau: jax.Array, source: jax.Array, valid: jax.Array) -> jax.Array:
    """Deterministic total order: valid first, then (tau, source, arrival)."""
    n = tau.shape[0]
    # two stable passes => lexicographic (tau, source); arrival order breaks
    # remaining ties because argsort is stable.
    order1 = jnp.argsort(source, stable=True)
    tau1 = jnp.where(valid, tau, wm.INF_TIME)[order1]
    order2 = jnp.argsort(tau1, stable=True)
    return order1[order2]


# The tie-break CONTRACT of merge_order, per backend.  Both keys are valid
# ScaleGate total orders: the ready *set* and the per-tau grouping are
# identical under either; only the order among equal-tau tuples differs.
# Nothing downstream may depend on the tie order beyond determinism: the
# hierarchical root merge (repro.ingest.root) re-sorts whatever its leaves
# forward, so leaves running different backends compose correctly, and
# tests/test_ingest_tier.py pins cross-backend parity on tied-tau batches.
TIE_BREAK = {
    "xla": ("tau", "source", "arrival"),
    "pallas": ("tau", "arrival"),
    "pallas-interpret": ("tau", "arrival"),
}


def tie_break(backend: str = None):
    """The documented sort key of ``merge_order`` under ``backend``
    (resolved), as a tuple of field names — lexicographic, most-significant
    first.  ``arrival`` is the lane index in the combined stash+incoming
    buffer, so both contracts are deterministic total orders."""
    from repro.kernels import dispatch
    return TIE_BREAK[dispatch.resolve(backend)]


def merge_order(tau: jax.Array, source: jax.Array, valid: jax.Array,
                n_sources: int, backend: str = None) -> jax.Array:
    """The merge's total order, via the kernel backend dispatcher.

    ``xla`` (the CPU default) keeps the lexicographic
    ``(tau, source, arrival)`` argsort.  The Pallas backends always run the
    ``scalegate_merge`` bitonic network, which orders by ``(tau, arrival)``
    and pads any batch length to a power-of-two (rows, 128) tile itself;
    both are valid ScaleGate total orders (see ``TIE_BREAK`` above).
    """
    from repro.kernels import dispatch

    if dispatch.resolve(backend) == "xla":
        return _stable_order(tau, source, valid)
    from repro.kernels.scalegate_merge.ops import scalegate_merge_op
    order, _, _ = scalegate_merge_op(tau, source, valid,
                                     n_sources=n_sources, backend=backend)
    return order


def push(state: ScaleGateState, incoming: T.TupleBatch, *,
         backend: str = None,
         wstate: wm.WatermarkState = None) -> Tuple[ScaleGateState, T.TupleBatch]:
    """Merge a tick of per-source tuples; emit the ready prefix.

    The emitted batch has static size ``capacity + incoming.batch`` with a
    validity mask selecting the ready tuples (sorted, exactly-once).
    ``backend`` selects the merge-sort realization (see ``merge_order``);
    the per-source watermark frontiers are stateful and always tracked here.

    ``wstate`` overrides the implicit per-tuple frontier fold with an
    externally computed ``WatermarkState`` — the hierarchical root merge
    (repro.ingest.root) gates on *explicitly reported* per-leaf watermarks
    (``wm.observe_explicit``) because its incoming tuples keep their
    original source ids for the downstream pipeline while the root's
    frontier axis is the leaf set.
    """
    cap = state.capacity
    combined = T.concat(state.stash, incoming)

    # addTuple: fold the new arrivals into the per-source frontiers.
    if wstate is None:
        wstate = wm.observe(state.wmark, incoming.source, incoming.tau,
                            incoming.valid)
    w = wstate.value()

    order = merge_order(combined.tau, combined.source, combined.valid,
                        state.wmark.n_sources, backend)
    merged = T.take(combined, order)

    ready = merged.valid & (merged.tau <= w)
    out = dataclasses.replace(merged, valid=ready)

    # Stash = the non-ready survivors, compacted to the front of the buffer.
    keep = merged.valid & ~ready
    # order: kept lanes first (stable, so timestamp order is preserved).
    keep_order = jnp.argsort(~keep, stable=True)
    n_keep = jnp.sum(keep.astype(jnp.int32))
    lanes = jnp.arange(cap)
    stash = T.take(merged, keep_order[:cap], fill_invalid=lanes >= n_keep)
    dropped = jnp.maximum(n_keep - cap, 0)

    new_state = ScaleGateState(
        stash=stash, wmark=wstate, overflow=state.overflow + dropped)
    return new_state, out


def push_stacked(state: ScaleGateState, stacked: T.TupleBatch,
                 reports: jax.Array, rmask: jax.Array, *,
                 backend: str = None) -> Tuple[ScaleGateState, T.TupleBatch]:
    """Fused root merge: one kernel call over stacked per-leaf chunk rows.

    ``stacked`` is a TupleBatch whose fields carry a leading ``[rows, C]``
    layout (each row one padded ready chunk from a leaf, rows in leaf
    order); ``reports``/``rmask`` are the per-leaf reported watermarks and
    report mask of this round.  The frontier fold, the Definition-3
    reduction and the merge all happen inside one traced program
    (``wm.fold_reports`` + ``scalegate_merge_stacked``), so the steady
    state round never syncs to host.  Requires ``capacity % C == 0`` so the
    stash prepends as whole rows.

    Emission order: ``(tau, arrival)`` with arrival = stash lanes first,
    then leaf rows in order — a valid ScaleGate total order under either
    TIE_BREAK contract (the ready *set* and tau grouping match ``push``
    exactly; only the order among equal-tau tuples may differ from the flat
    xla path's ``(tau, source, arrival)``).
    """
    from repro.kernels.scalegate_merge.ops import scalegate_merge_stacked_op

    cap = state.capacity
    rows, c = stacked.tau.shape
    assert cap % c == 0, (cap, c)

    wstate, eff, w = wm.fold_reports(state.wmark, reports, rmask)

    incoming = jax.tree.map(
        lambda a: a.reshape((rows * c,) + a.shape[2:]), stacked)
    combined = T.concat(state.stash, incoming)
    n = combined.batch
    order2, _, _ = scalegate_merge_stacked_op(
        combined.tau.reshape(n // c, c), combined.source.reshape(n // c, c),
        combined.valid.reshape(n // c, c).astype(jnp.int32), eff,
        backend=backend)
    merged = T.take(combined, order2.reshape(-1))

    ready = merged.valid & (merged.tau <= w)
    out = dataclasses.replace(merged, valid=ready)

    keep = merged.valid & ~ready
    keep_order = jnp.argsort(~keep, stable=True)
    n_keep = jnp.sum(keep.astype(jnp.int32))
    lanes = jnp.arange(cap)
    stash = T.take(merged, keep_order[:cap], fill_invalid=lanes >= n_keep)
    dropped = jnp.maximum(n_keep - cap, 0)

    new_state = ScaleGateState(
        stash=stash, wmark=wstate, overflow=state.overflow + dropped)
    return new_state, out


def add_sources(state: ScaleGateState, mask: jax.Array, gamma) -> ScaleGateState:
    """ESG addSources — Lemma 3: start the new frontier at gamma."""
    return dataclasses.replace(state, wmark=wm.add_sources(state.wmark, mask, gamma))


def remove_sources(state: ScaleGateState, mask: jax.Array) -> ScaleGateState:
    """ESG removeSources — flush semantics of §6."""
    return dataclasses.replace(state, wmark=wm.remove_sources(state.wmark, mask))


# ------------------------------------------------- checkpoint export/import --
_STASH_FIELDS = tuple(f.name for f in dataclasses.fields(T.TupleBatch))


def export_np(state: ScaleGateState) -> dict:
    """Host-side snapshot of a gate (stash + frontier + overflow) as a dict
    of plain numpy arrays: a checkpointable pytree that is also picklable
    across process-worker channels."""
    import numpy as np
    return {
        "stash": {f: np.asarray(getattr(state.stash, f))
                  for f in _STASH_FIELDS},
        "wmark": wm.export_np(state.wmark),
        "overflow": np.asarray(state.overflow),
    }


def import_np(d: dict) -> ScaleGateState:
    return ScaleGateState(
        stash=T.TupleBatch(**{f: jnp.asarray(d["stash"][f])
                              for f in _STASH_FIELDS}),
        wmark=wm.import_np(d["wmark"]),
        overflow=jnp.asarray(d["overflow"], jnp.int32),
    )


def template_np(n_sources: int, capacity: int, kmax: int,
                payload_width: int) -> dict:
    """Zero-filled ``export_np``-shaped dict: the restore ``like`` template
    for a gate with these dimensions."""
    return export_np(init_scalegate(n_sources, capacity, kmax,
                                    payload_width))
