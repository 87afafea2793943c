"""Pipeline driver: ScaleGate -> epoch handling -> executor tick (§7, Fig. 5).

``setup(O+, m, n)``: a pipeline is created with ``n_max`` instances of which
``n_active`` are connected (the rest are the paper's pool: active=False,
zero responsible keys, negligible work).  Each ``step``:

  1. (optional) a ``Reconfiguration`` from a controller is encapsulated in
     per-source control tuples stamped with the last forwarded tau
     (addSTRETCH, Alg. 5) and pushed with the data;
  2. ScaleGate merges and gates ready tuples (shared TB);
  3. prepareReconfig adopts pending tables (Alg. 6);
  4. the tick is processed in two epoch phases split at gamma (Alg. 4 L17):
     the tau-sorted prefix <= gamma under f_mu, the rest under f_mu*;
  5. outputs from all instances feed the downstream TB (Lemma 2/3 make the
     concatenation a valid sorted source set).

``VSNPipeline`` shares sigma (the paper); ``SNPipeline`` keeps dedicated
sigma_j and pays duplication + state transfer — the measured baseline.
``MeshPipeline`` is the VSN pipeline on a real device mesh: sigma sharded
over the instance axis in fixed key blocks (owner-computes), ScaleGate +
EpochState replicated, the whole step — including batched multi-tick
ingest (``lax.scan`` over T stacked ticks) — compiled into one
``shard_map`` call.  Output-set parity with ``VSNPipeline`` is exact,
including across a reconfiguration, and the compiled step moves zero
bytes of state between devices (Theorem 3 made physical).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import elastic, scalegate, sn, tuples as T, vsn
from repro.core import watermark as wm
from repro.core.controller import Reconfiguration
from repro.core.operator import OperatorDef, tick as general_tick


def fold_frontier(frontier: np.ndarray, b: T.TupleBatch,
                  n_inputs: int) -> None:
    """Fold one batch's per-source max data tau into a host-side frontier
    (mutated in place): the Alg. 5 bookkeeping behind control-tuple stamps,
    shared by the async runtime's tick metadata and the mesh driver."""
    tau = np.asarray(b.tau)
    src = np.asarray(b.source)
    ok = np.asarray(b.valid) & ~np.asarray(b.is_control)
    for i in range(n_inputs):
        sel = ok & (src == i)
        if sel.any():
            frontier[i] = max(frontier[i], int(tau[sel].max()))


def ctrl_lanes(n_inputs: int, frontier, epoch_id: int, kmax: int,
               p: int) -> T.TupleBatch:
    """One control tuple per source so every per-source stream stays
    sorted (Alg. 5); each stamped with that source's last forwarded tau."""
    lanes = []
    for i in range(n_inputs):
        c = elastic.make_control_tuple(int(frontier[i]), epoch_id, kmax, p)
        c = dataclasses.replace(c, source=jnp.asarray([i], jnp.int32))
        lanes.append(c)
    return functools.reduce(T.concat, lanes)


def inject_ctrl(inc_stack: T.TupleBatch, ctrl: T.TupleBatch, rc_tick,
                n_inputs: int) -> T.TupleBatch:
    """Overwrite the ctrl pad region (the last ``n_inputs`` lanes) of tick
    ``rc_tick`` in a staged [K, B] super-batch with ``ctrl``'s lanes.

    ``rc_tick`` may be a traced scalar, so ONE compiled persistent
    executable covers both the reconfig and the steady-state call: with no
    reconfiguration the caller passes an all-invalid ``ctrl`` (and any
    tick), making the update a proven no-op — the pad region is already
    all-invalid by construction (``stage_super``)."""
    def upd(stack_leaf, ctrl_leaf):
        start = ((rc_tick, stack_leaf.shape[1] - n_inputs)
                 + (0,) * (stack_leaf.ndim - 2))
        return jax.lax.dynamic_update_slice(
            stack_leaf, ctrl_leaf[None].astype(stack_leaf.dtype), start)
    return jax.tree.map(upd, inc_stack, ctrl)


@jax.jit
def _pad_stack(pad: T.TupleBatch, *batches: T.TupleBatch) -> T.TupleBatch:
    """Append the all-invalid ctrl pad to each of K same-shape ticks and
    stack them into one [K, B] super-batch in ONE compiled call — staging
    must stay far cheaper than a tick, and the host-side alternative
    (K x n_fields separate concat/stack dispatches) is not."""
    padded = [T.concat(b, pad) for b in batches]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *padded)


@dataclasses.dataclass
class PersistentOut:
    """Host-visible result of one persistent K-tick run.  The data lane
    (``outs_pre``/``outs_post``) stays a device array stack of leading dim
    K until the sink materializes it; the rest is the control lane:
    per-tick switch flags, watermark reports and (VSN only) per-instance
    loads."""
    outs_pre: Any                  # [K, ...] per-tick pre-phase outputs
    outs_post: Any                 # [K, ...] per-tick post-phase outputs
    switched: jax.Array            # bool[K]  epoch switch per tick
    wmark: jax.Array               # i32[K]   watermark report per tick
    inst_load: Any = None          # i32[K, n_max] or None (mesh)


@dataclasses.dataclass
class VSNPipeline:
    op: OperatorDef
    n_max: int
    n_active: int
    stash_cap: int = 256
    tick_fn: Callable = None
    merge_fn: Callable = None
    init_sigma: Callable = None
    # step_staged returns a device-computed per-instance load vector (the
    # async runtime then skips its host-side key-histogram fallback)
    device_inst_load = True

    def __post_init__(self):
        self.op = self.op.resolved()
        k = self.op.k_virt
        fmu = jnp.asarray(np.arange(k) % self.n_active, jnp.int32)
        active = jnp.asarray(
            np.arange(self.n_max) < self.n_active, bool)
        self.epoch = elastic.init_epoch(fmu, active)
        self.sigma = (self.init_sigma or self.op.init_state)()
        self.sg = scalegate.init_scalegate(
            self.op.n_inputs, self.stash_cap, 1,
            self.op.payload_out if False else 1)  # placeholder, reset below
        self._tick = self.tick_fn or general_tick
        self._merge = self.merge_fn or vsn.merge_states
        self._sg_ready = False
        self._step = jax.jit(self._step_impl)
        # persistent K-tick driver: donate the ScaleGate and sigma buffers
        # (args 0 and 2) so the scan updates them in place; epoch is NEVER
        # donated — with no reconfiguration ``fmu_new`` aliases its tables.
        self._persistent = jax.jit(self._persistent_impl,
                                   donate_argnums=(0, 2))
        self._persistent_structs = {}
        self._empty_ctrl = {}          # (kmax, p) -> steady-state (ctrl, rc)

    def _ensure_gate(self, incoming: T.TupleBatch):
        if not self._sg_ready:
            self.sg = scalegate.init_scalegate(
                self.op.n_inputs, self.stash_cap, incoming.kmax,
                incoming.payload_width)
            self._sg_ready = True

    def ensure_gate_for(self, kmax: int, payload_width: int):
        """Initialize the gate from dimensions alone (no data tick yet) —
        the restore path needs a fully-shaped state template before any
        tuple has been staged."""
        self._ensure_gate(T.empty_batch(1, kmax, payload_width))

    # -- checkpoint/restore ------------------------------------------------
    def export_state(self) -> dict:
        """The pipeline's epoch-consistent mutable state at a tick boundary
        (ScaleGate stash + watermark, EpochState incl. any pending
        ``e_next``/``fmu_next`` switch, sigma) as one checkpointable pytree.
        The caller must materialize it to host (``np.asarray``) before the
        next dispatch — ``run_persistent_staged`` donates sg and sigma."""
        assert self._sg_ready, "export_state() before the first staged tick"
        return {"sg": self.sg, "epoch": self.epoch, "sigma": self.sigma}

    def import_state(self, state: dict):
        """Install a snapshot produced by ``export_state`` (possibly via a
        checkpoint roundtrip).  Counterpart of ``export_state``; the epoch
        shadow state readers (async runtime) re-derive from ``self.epoch``."""
        self.sg = jax.tree.map(jnp.asarray, state["sg"])
        self.epoch = jax.tree.map(jnp.asarray, state["epoch"])
        self.sigma = jax.tree.map(jnp.asarray, state["sigma"])
        self._sg_ready = True

    def _inst_load(self, ready: T.TupleBatch, epoch) -> jax.Array:
        """Per-instance load of one tick under the in-effect f_mu: one unit
        per (valid data lane, key-set entry) routed to its owner — the
        live signal the elasticity controllers consume (§8.4)."""
        data = ready.valid & ~ready.is_control
        kmask = data[:, None] & (ready.keys != T.NO_KEY)
        owners = epoch.fmu[jnp.clip(ready.keys, 0, None)]
        return jnp.zeros((self.n_max,), jnp.int32
                         ).at[owners].add(kmask.astype(jnp.int32))

    def _tick_with_epoch(self, sigma, ready, epoch):
        return vsn.run_tick(self.op, sigma, ready, epoch.fmu, epoch.active,
                            self._tick, self._merge)

    def _step_impl(self, sg, epoch, sigma, incoming, fmu_new, active_new):
        (sg, epoch, sigma, outs1, outs2, switched, _wmk,
         inst_load) = vsn.pipeline_tick(sg, epoch, sigma, incoming, fmu_new,
                                        active_new, self._tick_with_epoch,
                                        self._inst_load)
        return sg, epoch, sigma, outs1, outs2, switched, inst_load

    def _persistent_impl(self, sg, epoch, sigma, inc_stack, ctrl, rc_tick,
                         fmu_new, active_new):
        """K ticks inside one ``lax.scan``: only the control lane (switch
        flags, watermark reports, instance loads) and the stacked output
        buffers leave the compiled program — no per-tick host round-trip,
        no per-tick dispatch."""
        inc_stack = inject_ctrl(inc_stack, ctrl, rc_tick, self.op.n_inputs)

        def body(carry, incoming):
            sg, epoch, sigma = carry
            sg, epoch, sigma, o1, o2, sw, wmk, il = vsn.pipeline_tick(
                sg, epoch, sigma, incoming, fmu_new, active_new,
                self._tick_with_epoch, self._inst_load)
            return (sg, epoch, sigma), (o1, o2, sw, wmk, il)

        (sg, epoch, sigma), (o1, o2, sw, wmk, il) = jax.lax.scan(
            body, (sg, epoch, sigma), inc_stack)
        return sg, epoch, sigma, o1, o2, sw, wmk, il

    def stage(self, incoming: T.TupleBatch) -> T.TupleBatch:
        """Asynchronously place a tick on the device (async ingest: the
        ``device_put`` of tick T+1 overlaps device compute of tick T)."""
        self._ensure_gate(incoming)
        return jax.device_put(incoming)

    def step_staged(self, staged: T.TupleBatch,
                    reconfig: Optional[Reconfiguration] = None,
                    frontier=None):
        """``step`` on a pre-staged device batch; returns the extended
        ``(outs_pre, outs_post, switched, inst_load)``.

        ``frontier`` (host i32[n_inputs]: last forwarded tau per source) lets
        a control tuple be stamped without reading ``sg.wmark`` back from
        the device — a read that would block on the still-in-flight previous
        step and serialize the async loop.  When None, the device state is
        consulted (the synchronous path's behavior).
        """
        self._ensure_gate(staged)
        if reconfig is not None:
            if frontier is None:
                frontier = np.asarray(self.sg.wmark.frontier)
            from repro import obs as _obs
            _obs.counter_inc("pipeline.ctrl_injections")
            incoming = T.concat(staged, ctrl_lanes(
                self.op.n_inputs, frontier, reconfig.epoch, staged.kmax,
                staged.payload_width))
            fmu_new = jnp.asarray(reconfig.fmu)
            active_new = jnp.asarray(reconfig.active)
        else:
            pad = T.empty_batch(self.op.n_inputs, staged.kmax,
                                staged.payload_width)
            incoming = T.concat(staged, pad)
            fmu_new = self.epoch.fmu
            active_new = self.epoch.active
        (self.sg, self.epoch, self.sigma, outs1, outs2, switched,
         inst_load) = self._step(self.sg, self.epoch, self.sigma, incoming,
                                 fmu_new, active_new)
        return outs1, outs2, switched, inst_load

    def step(self, incoming: T.TupleBatch,
             reconfig: Optional[Reconfiguration] = None):
        """Push one tick; returns (outputs_pre, outputs_post, switched)."""
        outs1, outs2, switched, _ = self.step_staged(incoming, reconfig)
        return outs1, outs2, switched

    # -- persistent K-tick driver ------------------------------------------
    def _frontier_after(self, batches, frontier0=None):
        """Per-source last forwarded tau once ``batches`` have been pushed
        (the Alg. 5 stamp for a control tuple injected after them);
        ``frontier0`` avoids the blocking ``sg.wmark`` readback."""
        frontier = (np.asarray(frontier0).copy() if frontier0 is not None
                    else np.asarray(self.sg.wmark.frontier).copy())
        for b in batches:
            fold_frontier(frontier, b, self.op.n_inputs)
        return frontier

    def stage_super(self, batches) -> T.TupleBatch:
        """Stack K same-shape ticks — each with its all-invalid ctrl pad
        region appended — into one [K, B] device-resident super-batch (one
        transfer for the whole scan; ``inject_ctrl`` later rewrites the pad
        of at most one tick)."""
        batches = list(batches)
        assert batches, "empty super-batch"
        self._ensure_gate(batches[0])
        kmax, p = batches[0].kmax, batches[0].payload_width
        pad = T.empty_batch(self.op.n_inputs, kmax, p)
        return _pad_stack(pad, *batches)

    def run_persistent_staged(self, stack: T.TupleBatch,
                              reconfig: Optional[Reconfiguration] = None,
                              reconfig_at: int = 0,
                              frontier=None) -> PersistentOut:
        """The persistent scan over a pre-staged super-batch.  A reconfig's
        control tuples are injected into the ctrl pad lanes of tick
        ``reconfig_at`` *inside* the compiled program, so the mid-scan
        f_mu switch happens with zero state transfer and zero restaging;
        ``frontier`` must then be the per-source last-forwarded-tau AFTER
        the ticks preceding ``reconfig_at`` (see ``run_persistent``)."""
        kmax = stack.keys.shape[-1]
        p = stack.payload.shape[-1]
        if reconfig is not None:
            if frontier is None:
                frontier = np.asarray(self.sg.wmark.frontier)
            ctrl = ctrl_lanes(self.op.n_inputs, frontier, reconfig.epoch,
                              kmax, p)
            rc = jnp.asarray(max(reconfig_at, 0), jnp.int32)
            fmu_new = jnp.asarray(reconfig.fmu)
            active_new = jnp.asarray(reconfig.active)
        else:
            # the steady-state (no-reconfig) operands are call-invariant;
            # rebuilding them per dispatch would tax every super-batch
            if (kmax, p) not in self._empty_ctrl:
                self._empty_ctrl[(kmax, p)] = (
                    T.empty_batch(self.op.n_inputs, kmax, p),
                    jnp.zeros((), jnp.int32))
            ctrl, rc = self._empty_ctrl[(kmax, p)]
            fmu_new = self.epoch.fmu
            active_new = self.epoch.active
        args = (self.sg, self.epoch, self.sigma, stack, ctrl, rc, fmu_new,
                active_new)
        key = (stack.tau.shape[0], stack.tau.shape[1], kmax, p)
        if key not in self._persistent_structs:
            self._persistent_structs[key] = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        (self.sg, self.epoch, self.sigma, o1, o2, sw, wmk,
         il) = self._persistent(*args)
        return PersistentOut(outs_pre=o1, outs_post=o2, switched=sw,
                             wmark=wmk, inst_load=il)

    def run_persistent(self, batches,
                       reconfig: Optional[Reconfiguration] = None,
                       reconfig_at: int = 0,
                       frontier0=None) -> PersistentOut:
        """Run K ticks inside ONE compiled ``lax.scan`` with donated
        ScaleGate and sigma buffers: steady-state data never crosses the
        host boundary between ticks (``persistent_hlo`` + ``launch.mesh.
        host_transfer_ops`` is the witness).  Tick-for-tick identical to K
        sequential ``step`` calls, including a mid-scan reconfiguration."""
        batches = list(batches)
        assert batches, "empty super-batch"
        self._ensure_gate(batches[0])
        frontier = None
        if reconfig is not None:
            frontier = self._frontier_after(batches[:max(reconfig_at, 0)],
                                            frontier0)
        stack = self.stage_super(batches)
        return self.run_persistent_staged(stack, reconfig=reconfig,
                                          reconfig_at=reconfig_at,
                                          frontier=frontier)

    def persistent_hlo(self) -> str:
        """Compiled HLO of every persistent executable built so far — feed
        to ``launch.mesh.host_transfer_ops`` to prove the data lane stays
        on device for the whole scan."""
        texts = []
        for structs in self._persistent_structs.values():
            texts.append(self._persistent.lower(
                *structs).compile().as_text())
        return "\n".join(texts)


@dataclasses.dataclass
class SNPipeline:
    """The shared-nothing baseline: dedicated sigma_j, duplication at
    forward, state transfer at reconfiguration."""
    op: OperatorDef
    n_max: int
    n_active: int
    stash_cap: int = 256
    tick_fn: Callable = None

    def __post_init__(self):
        self.op = self.op.resolved()
        k = self.op.k_virt
        fmu = jnp.asarray(np.arange(k) % self.n_active, jnp.int32)
        active = jnp.asarray(np.arange(self.n_max) < self.n_active, bool)
        self.epoch = elastic.init_epoch(fmu, active)
        self.sigmas = sn.init_states(self.op, self.n_max)
        self._tick = self.tick_fn or general_tick
        self._sg_ready = False
        self.bytes_transferred = 0
        self.duplication = []
        self._step = jax.jit(self._step_impl)

    def _ensure_gate(self, incoming: T.TupleBatch):
        if not self._sg_ready:
            self.sg = scalegate.init_scalegate(
                self.op.n_inputs, self.stash_cap, incoming.kmax,
                incoming.payload_width)
            self._sg_ready = True

    def _step_impl(self, sg, epoch, sigmas, incoming, fmu_new, active_new):
        sg, ready = scalegate.push(sg, incoming)
        epoch = elastic.prepare_reconfig(epoch, ready, fmu_new, active_new)
        pre, post = elastic.split_epoch_masks(epoch, ready)

        dup = sn.duplication_factor(
            dataclasses.replace(ready, valid=pre), epoch.fmu, epoch.active)
        ready_pre = dataclasses.replace(
            ready, valid=pre | (ready.is_control & ready.valid))
        sigmas, outs1 = sn.run_tick(self.op, sigmas, ready_pre, epoch.fmu,
                                    epoch.active, self._tick)

        live = ready.valid & ~ready.is_control
        w_end = jnp.max(jnp.where(live, ready.tau, 0))
        fmu_old = epoch.fmu
        epoch, switched = elastic.advance_epoch(epoch, w_end)
        # SN pays the state transfer when ownership changes (§2.5):
        sigmas, moved_bytes = jax.lax.cond(
            switched,
            lambda s: elastic.sn_transfer(s, fmu_old, epoch.fmu),
            lambda s: (s, jnp.zeros((), jnp.int32)),
            sigmas)

        ready_post = dataclasses.replace(ready, valid=post)
        sigmas, outs2 = sn.run_tick(self.op, sigmas, ready_post, epoch.fmu,
                                    epoch.active, self._tick)
        return sg, epoch, sigmas, outs1, outs2, switched, dup, moved_bytes

    def step(self, incoming: T.TupleBatch,
             reconfig: Optional[Reconfiguration] = None):
        self._ensure_gate(incoming)
        if reconfig is not None:
            ctrls = []
            for i in range(self.op.n_inputs):
                tau_i = int(np.asarray(self.sg.wmark.frontier)[i])
                c = elastic.make_control_tuple(
                    tau_i, reconfig.epoch, incoming.kmax,
                    incoming.payload_width)
                c = dataclasses.replace(c, source=jnp.asarray([i], jnp.int32))
                ctrls.append(c)
            incoming = functools.reduce(T.concat, ctrls, incoming)
            fmu_new = jnp.asarray(reconfig.fmu)
            active_new = jnp.asarray(reconfig.active)
        else:
            pad = T.empty_batch(self.op.n_inputs, incoming.kmax,
                                incoming.payload_width)
            incoming = T.concat(incoming, pad)
            fmu_new = self.epoch.fmu
            active_new = self.epoch.active
        (self.sg, self.epoch, self.sigmas, outs1, outs2, switched, dup,
         moved) = self._step(self.sg, self.epoch, self.sigmas, incoming,
                             fmu_new, active_new)
        self.duplication.append(float(dup))
        self.bytes_transferred += int(moved)
        return outs1, outs2, switched


@dataclasses.dataclass
class MeshPipeline:
    """The VSN pipeline executed on a device mesh (paper §5 at scale-up).

    sigma is sharded over ``mesh``'s ``axis`` in fixed contiguous key
    blocks; every other piece of state (ScaleGate stash + watermark
    frontiers, EpochState tables) is replicated — each device runs the
    identical merge over the identical incoming tuples, so the shared-TB
    contract holds with zero communication.  An ``f_mu`` reconfiguration
    swaps replicated tables only: no sigma row ever crosses a device
    (``collective_bytes()`` proves it from the compiled HLO).

    ``mode``:
      * ``"general"``  — the O+ oracle tick (operator.tick) per key block;
      * ``"fast-agg"`` — the vectorized commutative-reducer fast path
                         (aggregate.tick_fast, ``agg_kind`` in count|sum|max).

    ``run([b0, b1, ...])`` is the batched ingest: the T ticks are stacked
    and scanned inside one compiled shard_map call, so the hot loop does
    not round-trip to Python per tick.  ``step(b)`` is the T=1 view with
    the VSNPipeline return convention.
    """
    op: OperatorDef
    mesh: Any
    axis: str = "i"
    stash_cap: int = 256
    mode: str = "general"
    agg_kind: str = "count"
    backend: str = None          # kernel backend for the fast-agg scatter
    n_max: int = None            # logical instance count (tables); defaults
    n_active: int = None         # to the shard count
    # the mesh step keeps zero extra replicated outputs: per-instance load
    # comes from the async runtime's host-side key histogram instead
    device_inst_load = False

    def __post_init__(self):
        self.op = self.op.resolved()
        self.n_shards = self.mesh.shape[self.axis]
        if self.op.k_virt % self.n_shards:
            raise ValueError(f"k_virt={self.op.k_virt} must divide over "
                             f"{self.n_shards} shards")
        self.n_max = self.n_max or self.n_shards
        self.n_active = self.n_active or self.n_max
        k = self.op.k_virt
        fmu = jnp.asarray(np.arange(k) % self.n_active, jnp.int32)
        active = jnp.asarray(np.arange(self.n_max) < self.n_active, bool)
        self.epoch = elastic.init_epoch(fmu, active)
        if self.mode == "general":
            if self.op.lazy_expiry:
                # lazy-expiry operators (ScaleJoin) purge/store inside f_U
                # with global-key semantics that localize_op cannot slice;
                # the mesh route for them is vsn.join_local_tick.
                raise ValueError(
                    "MeshPipeline mode='general' does not support "
                    "lazy-expiry operators (ScaleJoin): use "
                    "vsn.shard_tick with vsn.join_local_tick")
            sigma = self.op.init_state()
            make_local = vsn.general_local_tick(self.op)
        elif self.mode == "fast-agg":
            from repro.core.aggregate import fast_init
            sigma = fast_init(self.op)
            make_local = vsn.fast_agg_local_tick(self.op, self.agg_kind,
                                                 self.backend)
        else:
            raise ValueError(f"unknown mode {self.mode!r}")
        self.sigma = vsn.mesh_device_put(sigma, self.mesh, self.axis, k)
        self._step_fn = vsn.shard_pipeline_step(self.op, self.mesh, self.axis,
                                                make_local, sigma)
        self._jit = jax.jit(self._step_fn)   # one jit; it caches per shape
        # persistent variant: ctrl injection fused into the compiled call,
        # sigma (the only big buffer; arg 2) donated.  sg/epoch are small
        # replicated tables and stay undonated (fmu_new may alias epoch).
        self._persistent = jax.jit(self._persistent_fn, donate_argnums=(2,))
        self._persistent_structs = {}
        self.last_wmarks = None              # i32[T] of the latest run
        self._sg_ready = False
        # abstract (shape+sharding) args per step variant, for the lazy
        # collective_bytes lowering — never pins device buffers
        self._arg_structs = {}

    def _persistent_fn(self, sg, epoch, sigma, inc_stack, ctrl, rc_tick,
                       fmu_new, active_new):
        inc_stack = inject_ctrl(inc_stack, ctrl, rc_tick, self.op.n_inputs)
        return self._step_fn(sg, epoch, sigma, inc_stack, fmu_new,
                             active_new)

    # -- plumbing ----------------------------------------------------------
    def _ensure_gate(self, incoming: T.TupleBatch):
        if not self._sg_ready:
            self.sg = scalegate.init_scalegate(
                self.op.n_inputs, self.stash_cap, incoming.kmax,
                incoming.payload_width)
            self._sg_ready = True

    def ensure_gate_for(self, kmax: int, payload_width: int):
        """Initialize the gate from dimensions alone (restore templates)."""
        self._ensure_gate(T.empty_batch(1, kmax, payload_width))

    # -- checkpoint/restore ------------------------------------------------
    def export_state(self) -> dict:
        """Same contract as ``VSNPipeline.export_state``.  ``np.asarray``
        on the key-block-sharded sigma gathers the shards, so the snapshot
        the checkpoint layer materializes is the full logical array."""
        assert self._sg_ready, "export_state() before the first staged tick"
        return {"sg": self.sg, "epoch": self.epoch, "sigma": self.sigma}

    def import_state(self, state: dict):
        """Install a snapshot: sg/epoch re-replicated across the mesh,
        sigma re-sharded into fixed key blocks (``vsn.mesh_device_put``) —
        a snapshot taken on N devices restores onto any divisor mesh."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(self.mesh, P())
        self.sg = jax.tree.map(
            lambda a: jax.device_put(jnp.asarray(a), rep), state["sg"])
        self.epoch = jax.tree.map(
            lambda a: jax.device_put(jnp.asarray(a), rep), state["epoch"])
        host_sigma = jax.tree.map(np.asarray, state["sigma"])
        self.sigma = vsn.mesh_device_put(host_sigma, self.mesh, self.axis,
                                         self.op.k_virt)
        self._sg_ready = True

    def _frontier_after(self, batches, frontier0=None):
        """Per-source last forwarded tau once ``batches`` have been pushed:
        the Alg. 5 stamp for a control tuple injected after them.
        ``frontier0`` (host-tracked) avoids the device readback of
        ``sg.wmark`` that would block on the in-flight step."""
        frontier = (np.asarray(frontier0).copy() if frontier0 is not None
                    else np.asarray(self.sg.wmark.frontier).copy())
        for b in batches:
            fold_frontier(frontier, b, self.op.n_inputs)
        return frontier

    # -- the driver --------------------------------------------------------
    def stage(self, incoming: T.TupleBatch) -> T.TupleBatch:
        """Asynchronously replicate a tick across the mesh (async ingest:
        the transfer of tick T+1 overlaps device compute of tick T)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        self._ensure_gate(incoming)
        rep = NamedSharding(self.mesh, P())
        return jax.tree.map(lambda a: jax.device_put(a, rep), incoming)

    def step_staged(self, staged: T.TupleBatch,
                    reconfig: Optional[Reconfiguration] = None,
                    frontier=None):
        """One pre-staged tick with the extended return convention
        ``(outs_pre, outs_post, switched, inst_load)``; ``inst_load`` is
        None here (the async runtime derives it host-side from the tick's
        key histogram — the mesh step keeps zero extra replicated outputs).
        ``frontier`` as in ``VSNPipeline.step_staged``."""
        o1, o2, sw = self.run([staged], reconfig=reconfig,
                              frontier0=frontier)
        return o1, o2, sw[0], None

    def run(self, batches, reconfig: Optional[Reconfiguration] = None,
            reconfig_at: int = 0, frontier0=None):
        """Push T ticks in one compiled call; an optional reconfiguration is
        injected as control tuples riding with tick ``reconfig_at`` (Alg. 5:
        stamped with each source's last forwarded tau at that point).

        Returns ``(outs_pre, outs_post, switched)`` with leading tick axis T
        and the per-shard output lanes concatenated on axis 1.
        """
        batches = list(batches)
        assert batches, "empty tick stack"
        self._ensure_gate(batches[0])
        b0 = batches[0]
        kmax, p = b0.kmax, b0.payload_width

        padded = []
        for t, b in enumerate(batches):
            if reconfig is not None and t == reconfig_at:
                frontier = self._frontier_after(batches[:t], frontier0)
                pad = ctrl_lanes(self.op.n_inputs, frontier, reconfig.epoch,
                                 kmax, p)
            else:
                pad = T.empty_batch(self.op.n_inputs, kmax, p)
            padded.append(T.concat(b, pad))
        inc_stack = jax.tree.map(lambda *xs: jnp.stack(xs), *padded)

        if reconfig is not None:
            fmu_new = jnp.asarray(reconfig.fmu)
            active_new = jnp.asarray(reconfig.active)
        else:
            fmu_new = self.epoch.fmu
            active_new = self.epoch.active

        key = (len(padded), padded[0].batch, kmax, p)
        args = (self.sg, self.epoch, self.sigma, inc_stack, fmu_new,
                active_new)
        # re-captured every call so collective_bytes lowers the steady-state
        # variant (first-call inputs arrive host-placed, later ones carry
        # the replicated shardings of the previous step's outputs).  Only
        # mesh shardings are kept: a host-placed (single-device) input is
        # uncommitted in the real call, but abstract lowering would treat
        # it as pinned and reject the device mix.
        from jax.sharding import NamedSharding

        def struct(a):
            sh = getattr(a, "sharding", None)
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=sh if isinstance(sh, NamedSharding) else None)

        self._arg_structs[key] = jax.tree.map(struct, args)
        (self.sg, self.epoch, self.sigma, outs1, outs2, switched,
         wmk) = self._jit(*args)
        self.last_wmarks = wmk
        return outs1, outs2, switched

    def step(self, incoming: T.TupleBatch,
             reconfig: Optional[Reconfiguration] = None):
        """One tick, VSNPipeline-style: returns (outs_pre, outs_post,
        switched) with the T=1 axis kept on the outputs."""
        outs1, outs2, switched = self.run([incoming], reconfig=reconfig)
        return outs1, outs2, switched[0]

    # -- persistent K-tick driver ------------------------------------------
    def stage_super(self, batches) -> T.TupleBatch:
        """Stack K ticks (each with its all-invalid ctrl pad region) and
        replicate the [K, B] super-batch across the mesh in one transfer."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        batches = list(batches)
        assert batches, "empty super-batch"
        self._ensure_gate(batches[0])
        kmax, p = batches[0].kmax, batches[0].payload_width
        pad = T.empty_batch(self.op.n_inputs, kmax, p)
        stack = _pad_stack(pad, *batches)
        rep = NamedSharding(self.mesh, P())
        return jax.tree.map(lambda a: jax.device_put(a, rep), stack)

    def run_persistent_staged(self, stack: T.TupleBatch,
                              reconfig: Optional[Reconfiguration] = None,
                              reconfig_at: int = 0,
                              frontier=None) -> PersistentOut:
        """As ``VSNPipeline.run_persistent_staged``, on the mesh: the ctrl
        injection, the K-tick scan and the sharded two-phase ticks are one
        compiled call with donated sigma.  ``inst_load`` is None (the mesh
        step keeps zero extra replicated outputs)."""
        from jax.sharding import NamedSharding

        kmax = stack.keys.shape[-1]
        p = stack.payload.shape[-1]
        if reconfig is not None:
            if frontier is None:
                frontier = np.asarray(self.sg.wmark.frontier)
            ctrl = ctrl_lanes(self.op.n_inputs, frontier, reconfig.epoch,
                              kmax, p)
            rc = jnp.asarray(max(reconfig_at, 0), jnp.int32)
            fmu_new = jnp.asarray(reconfig.fmu)
            active_new = jnp.asarray(reconfig.active)
        else:
            ctrl = T.empty_batch(self.op.n_inputs, kmax, p)
            rc = jnp.zeros((), jnp.int32)
            fmu_new = self.epoch.fmu
            active_new = self.epoch.active
        args = (self.sg, self.epoch, self.sigma, stack, ctrl, rc, fmu_new,
                active_new)

        def struct(a):
            sh = getattr(a, "sharding", None)
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=sh if isinstance(sh, NamedSharding) else None)

        key = (stack.tau.shape[0], stack.tau.shape[1], kmax, p)
        self._persistent_structs[key] = jax.tree.map(struct, args)
        (self.sg, self.epoch, self.sigma, o1, o2, sw,
         wmk) = self._persistent(*args)
        self.last_wmarks = wmk
        return PersistentOut(outs_pre=o1, outs_post=o2, switched=sw,
                             wmark=wmk, inst_load=None)

    def run_persistent(self, batches,
                       reconfig: Optional[Reconfiguration] = None,
                       reconfig_at: int = 0,
                       frontier0=None) -> PersistentOut:
        """K ticks in one compiled, donated call on the mesh; tick-for-tick
        identical to ``run`` (they share the scan body) but with the ctrl
        injection on device and sigma updated in place."""
        batches = list(batches)
        assert batches, "empty super-batch"
        self._ensure_gate(batches[0])
        frontier = None
        if reconfig is not None:
            frontier = self._frontier_after(batches[:max(reconfig_at, 0)],
                                            frontier0)
        stack = self.stage_super(batches)
        return self.run_persistent_staged(stack, reconfig=reconfig,
                                          reconfig_at=reconfig_at,
                                          frontier=frontier)

    def persistent_hlo(self) -> str:
        """Compiled HLO of every persistent executable built so far (for
        ``launch.mesh.host_transfer_ops`` — the data lane must show zero
        host transfers)."""
        texts = []
        for structs in self._persistent_structs.values():
            texts.append(self._persistent.lower(
                *structs).compile().as_text())
        return "\n".join(texts)

    # -- accounting --------------------------------------------------------
    def collective_bytes(self):
        """Cross-device traffic of the compiled step(s), from the HLO: the
        zero-state-transfer witness (Theorem 3).  Returns {collective-kind:
        bytes} summed over every step variant compiled so far, batched
        (``run``) and persistent (``run_persistent_staged``) alike."""
        from repro.launch.mesh import collective_bytes as _cb

        texts = [self._jit.lower(*structs).compile().as_text()
                 for structs in self._arg_structs.values()]
        if self._persistent_structs:
            texts.append(self.persistent_hlo())
        total = {}
        for hlo in texts:
            for kind, b in _cb(hlo).items():
                total[kind] = total.get(kind, 0) + b
        return total

    def switch_bytes(self) -> int:
        """Bytes a reconfiguration actually moves: the replicated tables."""
        return elastic.vsn_switch_bytes(self.epoch)
