"""AsyncStreamRuntime: live double-buffered ingest + closed-loop elasticity.

The batch drivers (benchmarks, tests) pre-stage whole streams and pay a
host round-trip per tick.  This runtime makes the stream *live*:

* an **ingest thread** pulls ticks from an ``io`` source, computes the tiny
  host-side tick metadata (per-source frontier, tuple count, the keys the
  tick hits), and ``stage``s the batch onto the device — so the
  ``device_put`` of tick T+1 runs concurrently with device compute of
  tick T.  A ``BoundedQueue`` between the threads applies backpressure:
  the producer blocks, memory never grows past ``queue_cap`` ticks;
* the **step loop** dispatches the compiled ``VSNPipeline`` /
  ``MeshPipeline`` step on the staged batch and *never* blocks on the
  outputs (sinks keep device handles).  The only host syncs are the
  sampled metrics of the *previous* tick — the ``switched`` flag and the
  per-instance load vector — fetched while the current tick computes
  (double buffering).  With a controller in the loop they are fetched
  before the controller is asked instead: it then decides on the freshest
  metrics, and its decision rides a dispatch with no device work queued
  ahead of it, rather than waiting out the whole previous one;
* the **control loop** closes §8.4-§8.5: each tick, a ``MetricsBus``
  snapshot (offered/measured rate, per-instance load, queue depth) is fed
  to the controller, and an emitted ``Reconfiguration`` is injected
  mid-stream through the existing control-tuple path (Alg. 5), stamped
  from the *host-tracked* per-source frontier so no device readback stalls
  the loop.  Detection→switch latency (decision wall-clock to the first
  observed epoch switch) is measured per reconfiguration.

With tracing on, the spans split each side of the queue into work and
waiting: ``ingest.stage`` (work) and ``ingest.blocked`` (the put under
backpressure) on the ingest thread, tagged with the dispatch's first tick;
``runtime.wait`` (the get, starved of ingest), ``runtime.dispatch`` and
``runtime.drain`` (tagged with the dispatch's tick id, and the dispatch
with the epoch it injects) on the step loop.  A reconfiguration records
``reconfig.behind`` (decision to the drain of the dispatch before its own)
and ``reconfig.pending`` (decision to the drain that observes its switch).

``run_sync`` is the measured baseline: the same semantics as a plain
host loop (generate, step, block on outputs), so async-vs-sync throughput
isolates the overlap gain and async-vs-sync output sets pin correctness.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, List, Optional, Tuple

import numpy as np
import jax

from repro import obs as _obs
from repro.core import tuples as T
from repro.core.controller import Reconfiguration
from repro.core.runtime import fold_frontier
from repro.io.metrics import MetricsBus
from repro.io.queues import BoundedQueue, QueueClosed
from repro.io.sinks import CollectSink


@dataclasses.dataclass
class TickMeta:
    """Host-side facts about one tick, computed in the ingest thread."""
    tick_id: int
    n_tuples: int                  # valid data lanes
    frontier_before: np.ndarray    # i64[n_inputs] last tau per source BEFORE
    key_hits: Optional[np.ndarray]  # i32[hits] key of each (lane, key) hit


@dataclasses.dataclass
class StagedTick:
    meta: TickMeta
    staged: T.TupleBatch           # device-resident


@dataclasses.dataclass
class StagedSuper:
    """K consecutive same-shape ticks staged as one [K, B] super-batch for
    the pipeline's persistent compiled driver (``run_persistent_staged``).
    ``n_pad`` trailing ticks are all-invalid no-op fillers (a partial tail
    or an early flush on a shape change keeps one compiled K shape)."""
    metas: List[TickMeta]          # one per REAL tick, in order
    stack: T.TupleBatch            # device-resident [K, B] stack
    n_pad: int


@dataclasses.dataclass
class RunReport:
    ticks: int
    tuples: int
    wall_s: float
    throughput_tps: float
    p50_ms: float
    p99_ms: float
    queue_high_water: int
    blocked_puts: int
    reconfig_trace: List[Tuple[int, Reconfiguration]]
    switches: int
    detect_to_switch_ms: List[float]
    detect_to_switch_ticks: List[int]
    # detections whose switch never committed (flushed at stop())
    unresolved_detections: int = 0
    # per-stage latency breakdown {stage: {p50,p90,p99,mean,count}} in ms,
    # from span tracing when enabled (empty otherwise)
    stage_latency_ms: dict = dataclasses.field(default_factory=dict)
    # sampled per-tuple end-to-end timelines (admission -> ... -> emit),
    # when ObsConfig.exemplar_rate > 0 (empty otherwise)
    exemplar_timelines: list = dataclasses.field(default_factory=list)
    # SLO breaches observed during the run (SloBreach.to_dict() dicts),
    # when ObsConfig.slo_rules is set (empty otherwise)
    slo_breaches: list = dataclasses.field(default_factory=list)

    def summary(self) -> str:
        d2s = (f"{np.mean(self.detect_to_switch_ms):.1f}ms"
               f"/{np.mean(self.detect_to_switch_ticks):.1f}t"
               if self.detect_to_switch_ms else "n/a")
        return (f"{self.ticks} ticks, {self.tuples} tuples in "
                f"{self.wall_s:.2f}s = {self.throughput_tps:.0f} t/s; "
                f"tick latency p50={self.p50_ms:.2f}ms p99={self.p99_ms:.2f}ms; "
                f"{len(self.reconfig_trace)} reconfigs ({self.switches} "
                f"switched, detection->switch {d2s}); queue high-water "
                f"{self.queue_high_water}")


def _initial_frontier(pipeline, n_inputs: int) -> np.ndarray:
    """Seed the host-tracked frontier from the pipeline's ScaleGate state:
    a pre-warmed pipeline (e.g. a compile tick stepped before run()) has
    already forwarded taus, and control tuples stamped below them would
    violate the per-source sorted-stream invariant (Alg. 5).  Runs before
    the stream starts, so the device read cannot stall an in-flight step."""
    if getattr(pipeline, "_sg_ready", False):
        return np.asarray(pipeline.sg.wmark.frontier).astype(np.int64).copy()
    return np.zeros((n_inputs,), np.int64)


def make_report(metrics: MetricsBus, reconfig_trace, switches: int,
                queue=None, slo_breaches=None) -> RunReport:
    """Assemble the RunReport from a finished run's metrics (shared by the
    async loop and the run_sync baseline)."""
    p50, p99 = metrics.latency_quantiles_ms()
    o = _obs.get()
    return RunReport(
        ticks=metrics.n_ticks,
        tuples=metrics.total_tuples,
        wall_s=(metrics.t_end or 0.0) - (metrics.t_start or 0.0),
        throughput_tps=metrics.throughput_tps(),
        p50_ms=p50, p99_ms=p99,
        queue_high_water=0 if queue is None else queue.high_water,
        blocked_puts=0 if queue is None else queue.blocked_puts,
        reconfig_trace=list(reconfig_trace),
        switches=switches,
        detect_to_switch_ms=list(metrics.detect_to_switch_ms),
        detect_to_switch_ticks=list(metrics.detect_to_switch_ticks),
        unresolved_detections=len(metrics.unresolved_detections),
        stage_latency_ms=({} if o is None or not o.tracer.enabled
                          else o.tracer.stage_latency_ms()),
        exemplar_timelines=([] if o is None or o.timeline is None
                            else o.timeline.completed()),
        slo_breaches=[b.to_dict() for b in (slo_breaches or [])])


def tick_meta(b: T.TupleBatch, tick_id: int, n_inputs: int,
              frontier: np.ndarray, with_hits: bool = True) -> TickMeta:
    """Compute a tick's metadata and fold its taus into the running
    ``frontier`` (mutated) — numpy views only, no device work.

    The key histogram of the tick is kept sparse, as the key of each
    (valid lane, key) hit: O(B*KMAX) work and memory, whatever the key
    space (span ``ingest.key_hist``).  ``with_hits=False`` skips it: it is
    only consumed by the host-side load fallback for pipelines whose step
    does not return a device ``inst_load`` (MeshPipeline), and the ingest
    thread should stay as light as possible."""
    ok = np.asarray(b.valid) & ~np.asarray(b.is_control)
    before = frontier.copy()
    fold_frontier(frontier, b, n_inputs)
    hits = None
    if with_hits:
        with _obs.span("ingest.key_hist", tick=tick_id):
            keys = np.asarray(b.keys)
            hits = keys[ok[:, None] & (keys >= 0)]
    return TickMeta(tick_id, int(ok.sum()), before, hits)


class AsyncStreamRuntime:
    """Drive a pipeline from a live source with overlapped ingest and a
    controller in the loop.  ``pipeline`` must expose ``stage`` and
    ``step_staged`` (VSNPipeline and MeshPipeline do)."""

    def __init__(self, pipeline, source, sink=None, controller=None,
                 queue_cap: int = 4, metrics: Optional[MetricsBus] = None,
                 super_batch: int = 1, checkpointer=None, tick0: int = 0):
        self.pipeline = pipeline
        self.source = source
        # fault tolerance: ``checkpointer`` (a StreamCheckpointer) is asked
        # at every tick boundary, BEFORE the dispatch that donates the
        # pipeline state; ``tick0`` offsets tick ids on a resumed run so
        # sink tick ids and checkpoint steps stay absolute across restarts
        self.checkpointer = checkpointer
        self.tick0 = int(tick0)
        self.sink = sink if sink is not None else CollectSink()
        self.controller = controller
        # super_batch=K stages K consecutive same-shape ticks as ONE
        # device-resident stack and dispatches the pipeline's persistent
        # compiled K-tick scan instead of K step calls: one dispatch, one
        # control-lane sync, zero host crossings for the data lane.  The
        # controller still runs (once per super-batch); its reconfiguration
        # is injected into the scan's first tick on device.
        assert super_batch >= 1
        if super_batch > 1:
            assert hasattr(pipeline, "run_persistent_staged"), pipeline
        self.super_batch = super_batch
        self.queue = BoundedQueue(queue_cap)
        self.metrics = metrics or MetricsBus(queue_cap=queue_cap)
        # a caller-supplied bus must still know the in-flight cap, or the
        # controllers' queue-pressure term silently never fires
        self.metrics.queue_cap = self.metrics.queue_cap or queue_cap
        self.reconfig_trace: List[Tuple[int, Reconfiguration]] = []
        self.switches = 0
        # host shadows of the COMMITTED epoch tables (mesh load fallback +
        # the n_active a load sample is judged under); read once before the
        # stream starts, so no in-flight sync.  Pending (injected but not
        # yet switched) reconfigurations live in the MetricsBus, which
        # hands back what a switch committed.
        self._fmu_shadow = np.asarray(pipeline.epoch.fmu).copy()
        self._active_shadow = np.asarray(pipeline.epoch.active).copy()
        self._ingest_error: Optional[BaseException] = None
        # SLO breaches: _pending feeds the NEXT controller decision via
        # LiveMetrics.slo_breaches, _all accumulates for the RunReport
        self._pending_breaches: List = []
        self._all_breaches: List = []

    # -- ingest thread ------------------------------------------------------
    def _ingest(self, max_ticks: Optional[int]):
        n_inputs = self.pipeline.op.n_inputs
        # the tick's key hits are only needed for the host-side load
        # fallback (pipelines whose step doesn't return a device inst_load)
        with_hits = not getattr(self.pipeline, "device_inst_load", False)
        frontier = _initial_frontier(self.pipeline, n_inputs)
        try:
            if self.super_batch > 1:
                self._ingest_super(max_ticks, n_inputs, with_hits, frontier)
            else:
                for i, b in enumerate(self.source):
                    if max_ticks is not None and i >= max_ticks:
                        break
                    tick_id = self.tick0 + i
                    with _obs.span("ingest.stage", tick=tick_id):
                        meta = tick_meta(b, tick_id, n_inputs, frontier,
                                         with_hits=with_hits)
                        staged = self.pipeline.stage(b)   # async transfer
                    tl = _obs.exemplars()
                    if tl is not None:
                        ok = np.asarray(b.valid) & ~np.asarray(b.is_control)
                        tl.scan(np.asarray(b.source), np.asarray(b.tau),
                                ok, "stage", tick_id=meta.tick_id)
                    with _obs.span("ingest.blocked", tick=tick_id):
                        self.queue.put(StagedTick(meta, staged))
        except BaseException as e:              # surfaced after join()
            self._ingest_error = e
            _obs.event("ingest_error", error=repr(e))
        finally:
            self.queue.close()

    def _ingest_super(self, max_ticks, n_inputs: int, with_hits: bool,
                      frontier: np.ndarray):
        """Group up to ``super_batch`` consecutive same-shape ticks and
        stage each group as one device stack.  A shape change flushes the
        open group early; ``stage_super`` pads a partial group with
        all-invalid no-op ticks so every dispatch reuses ONE compiled
        K-tick executable."""
        K = self.super_batch
        group: List[T.TupleBatch] = []
        metas: List[TickMeta] = []
        gkey = None

        def flush():
            nonlocal group, metas
            if not group:
                return
            n_pad = K - len(group)
            tick_id = metas[0].tick_id
            with _obs.span("ingest.stage", tick=tick_id):
                stack = self.pipeline.stage_super(group, K)  # async transfer
            with _obs.span("ingest.blocked", tick=tick_id):
                self.queue.put(StagedSuper(metas=metas, stack=stack,
                                           n_pad=n_pad))
            group, metas = [], []

        for i, b in enumerate(self.source):
            if max_ticks is not None and i >= max_ticks:
                break
            key = (b.batch, b.kmax, b.payload_width)
            if group and key != gkey:
                flush()
            gkey = key
            metas.append(tick_meta(b, self.tick0 + i, n_inputs, frontier,
                                   with_hits=with_hits))
            tl = _obs.exemplars()
            if tl is not None:
                ok = np.asarray(b.valid) & ~np.asarray(b.is_control)
                # bind to the super-batch's decision tick (the first tick
                # id of the open group) — that is the id _drain sees
                tl.scan(np.asarray(b.source), np.asarray(b.tau), ok,
                        "stage", tick_id=metas[0].tick_id)
            group.append(b)
            if len(group) == K:
                flush()
        flush()

    @staticmethod
    def _combine_meta(metas: List[TickMeta]) -> TickMeta:
        """One decision-granularity view of a super-batch: tuple counts sum
        and key hits concatenate; the frontier stamp is the one BEFORE the
        first tick (the reconfiguration is injected there)."""
        hits = (None if metas[0].key_hits is None
                else np.concatenate([m.key_hits for m in metas]))
        return TickMeta(tick_id=metas[0].tick_id,
                        n_tuples=sum(m.n_tuples for m in metas),
                        frontier_before=metas[0].frontier_before,
                        key_hits=hits)

    # -- metric sampling ----------------------------------------------------
    def _host_inst_load(self, key_hits) -> Optional[np.ndarray]:
        """Per-instance load of the hits under the committed f_mu: one unit
        per hit routed to its owner, O(hits) whatever the key space."""
        if key_hits is None:
            return None
        n_max = self._active_shadow.shape[0]
        return np.bincount(self._fmu_shadow[key_hits],
                           minlength=n_max).astype(np.int64)

    def _drain(self, pending, idle_s: float = 0.0):
        """Fetch the sampled metrics of a completed tick (blocks only on the
        scalar ``switched`` flag and the tiny per-instance load vector).
        ``idle_s`` — time the loop spent waiting on the source for the NEXT
        tick — is subtracted so a paced/starved source does not inflate the
        reported tick latency."""
        tick_id, switched, inst_load, meta, t_dispatch = pending
        with _obs.span("runtime.drain", tick=tick_id):
            sw = bool(np.asarray(switched))
            load = (np.asarray(inst_load) if inst_load is not None
                    else self._host_inst_load(meta.key_hits))
        latency = max(time.perf_counter() - t_dispatch - idle_s, 0.0)
        _obs.event("tick", tick_id=tick_id, n_tuples=meta.n_tuples,
                   latency_ms=latency * 1e3, queue_depth=self.queue.depth,
                   queue_high_water=self.queue.high_water, switched=sw,
                   wmark_frontier=meta.frontier_before.tolist())
        # record BEFORE updating the shadows: this tick's load was measured
        # under the pre-switch tables, and the (inst_load, n_active) pair
        # must stay consistent or the controller reads phantom skew.
        self.metrics.record_tick(tick_id, meta.n_tuples, latency, load,
                                 self.queue.depth,
                                 n_active=int(self._active_shadow.sum()))
        o = _obs.get()
        if o is not None:
            if o.timeline is not None:
                # the tick's outputs are known delivered here: drain then
                # emit, completing this tick's exemplar timelines
                o.timeline.mark_tick(tick_id, "drain")
                o.timeline.mark_tick(tick_id, "emit")
            if o.slo is not None:
                # evaluate on the freshest tick-latency/drain quantiles;
                # breaches reach the controller at the next _decide
                new = o.evaluate_slo()
                if new:
                    self._pending_breaches.extend(new)
                    self._all_breaches.extend(new)
        if sw:
            self.switches += 1
            # the switch commits the LATEST rc injected by this tick; any
            # earlier ones it superseded are resolved with it.
            resolved = self.metrics.record_switch(tick_id)
            if resolved:
                rc = resolved[-1]
                self._fmu_shadow = np.asarray(rc.fmu).copy()
                self._active_shadow = np.asarray(rc.active).copy()
                _obs.event("switch", tick_id=tick_id, epoch=int(rc.epoch),
                           n_active=int(self._active_shadow.sum()))

    def _decide(self, meta: TickMeta
                ) -> Tuple[Optional[Reconfiguration], float]:
        """The controller's reconfiguration for this dispatch, if any, and
        the ``time.perf_counter`` stamp of its decision."""
        if self.controller is None:
            return None, 0.0
        hint = None
        if hasattr(self.source, "rate_hint"):
            hint = self.source.rate_hint(meta.tick_id)
        if hint is None and len(self.metrics.records) < 2:
            return None, 0.0   # no rate signal yet: a measured rate of 0.0
            # at stream start would read as idle and trigger a bogus
            # scale-down
        breaches = tuple(self._pending_breaches)
        self._pending_breaches.clear()
        snap = self.metrics.snapshot(
            rate_hint=hint, queue_depth=self.queue.depth,
            backlog_tuples=float(self.queue.depth * meta.n_tuples),
            slo_breaches=breaches)
        with _obs.span("controller.decide"):
            rc = self.controller.observe_live(snap)
            return rc, time.perf_counter()

    # -- the loop -----------------------------------------------------------
    def run(self, max_ticks: Optional[int] = None) -> RunReport:
        th = threading.Thread(target=self._ingest, args=(max_ticks,),
                              daemon=True)
        self.metrics.start()
        th.start()
        pending = None
        t_drained = 0.0          # when the latest drain returned
        try:
            while True:
                t_wait = time.perf_counter()
                try:
                    with _obs.span("runtime.wait"):
                        item = self.queue.get()
                except QueueClosed:     # ingest done and every tick drained
                    break
                idle_s = time.perf_counter() - t_wait
                if isinstance(item, StagedSuper):
                    meta = self._combine_meta(item.metas)
                else:
                    meta = item.meta
                if self.checkpointer is not None:
                    # the boundary BEFORE this tick: pipeline state covers
                    # every tick < meta.tick_id and nothing of this one;
                    # capture is synchronous-to-host (the dispatch below
                    # donates sg/sigma), the disk write is async
                    with _obs.span("runtime.checkpoint"):
                        self.checkpointer.maybe_save(meta.tick_id,
                                                     meta.frontier_before)
                follows = pending is not None
                if follows and self.controller is not None:
                    # decide on the previous tick's metrics, once the
                    # device has nothing queued ahead of this dispatch
                    self._drain(pending, idle_s=idle_s)
                    pending, t_drained = None, time.perf_counter()
                rc, t_decide = self._decide(meta)
                ids = {"tick": meta.tick_id}
                if rc is not None:
                    ids["epoch"] = int(rc.epoch)
                t0 = time.perf_counter()
                with _obs.span("runtime.dispatch", **ids):
                    if isinstance(item, StagedSuper):
                        out = self.pipeline.run_persistent_staged(
                            item.stack, reconfig=rc, reconfig_at=0,
                            frontier=meta.frontier_before)
                        o1, o2 = out.outs_pre, out.outs_post
                        switched = out.switched.any()
                        inst_load = (None if out.inst_load is None
                                     else out.inst_load.sum(axis=0))
                    else:
                        o1, o2, switched, inst_load = \
                            self.pipeline.step_staged(
                                item.staged, reconfig=rc,
                                frontier=meta.frontier_before)
                tl = _obs.exemplars()
                if tl is not None:
                    tl.mark_tick(meta.tick_id, "dispatch")
                if rc is not None:
                    self.reconfig_trace.append((meta.tick_id, rc))
                    self.metrics.record_detection(rc.epoch, meta.tick_id,
                                                  rc, t=t_decide)
                    _obs.event("reconfig", tick_id=meta.tick_id,
                               epoch=int(rc.epoch),
                               n_active=int(np.asarray(rc.active).sum()))
                self.sink.accept(meta.tick_id, o1, o2)
                if pending is not None:
                    # tick T-1 syncs while T computes; the wait for T's
                    # arrival was source idle time, not T-1's latency
                    self._drain(pending, idle_s=idle_s)
                    t_drained = time.perf_counter()
                if follows and rc is not None:
                    # the device work queued ahead of this dispatch
                    _obs.interval("reconfig.behind", t_decide,
                                  max(t_decide, t_drained),
                                  epoch=int(rc.epoch))
                pending = (meta.tick_id, switched, inst_load, meta, t0)
            if pending is not None:
                self._drain(pending)
        except BaseException as e:
            # failures come with a timeline, not just a stack trace: stamp
            # the crash into the ring and dump it (when a dump_dir is
            # configured) before unwinding
            _obs.event("runtime_crash", error=repr(e))
            o = _obs.get()
            if o is not None:
                o.dump_flight(reason=f"runtime_crash: {e!r}")
            raise
        finally:
            # on error the ingest thread may be parked in put(); closing
            # the queue releases it so nothing (thread or staged device
            # buffers) outlives the run
            self.queue.close()
            self.metrics.stop()
            th.join(timeout=30)
            if self.checkpointer is not None:
                self.checkpointer.wait()   # never exit with a torn save
        if self._ingest_error is not None:
            o = _obs.get()
            if o is not None:
                o.dump_flight(
                    reason=f"ingest_error: {self._ingest_error!r}")
            raise self._ingest_error
        return make_report(self.metrics, self.reconfig_trace, self.switches,
                           queue=self.queue, slo_breaches=self._all_breaches)


def run_sync(pipeline, source, sink=None, controller=None,
             max_ticks: Optional[int] = None,
             reconfig_trace=None) -> Tuple[RunReport, Any]:
    """The synchronous host-loop baseline: generate a tick, step, block on
    the outputs, repeat.  Same semantics as the async loop (same control
    tuples, same frontier stamps) minus every overlap — the reference both
    for the throughput comparison and for output-set parity.

    ``reconfig_trace`` replays a recorded ``[(tick_id, Reconfiguration)]``
    (e.g. from an async run) instead of consulting ``controller``, so a
    parity check can hold the reconfiguration sequence fixed.
    """
    sink = sink if sink is not None else CollectSink()
    metrics = MetricsBus(queue_cap=0)
    n_inputs = pipeline.op.n_inputs
    frontier = _initial_frontier(pipeline, n_inputs)
    replay = dict(reconfig_trace) if reconfig_trace is not None else None
    trace: List[Tuple[int, Reconfiguration]] = []
    switches = 0
    active_shadow = np.asarray(pipeline.epoch.active).copy()
    metrics.start()
    for tick_id, b in enumerate(source):
        if max_ticks is not None and tick_id >= max_ticks:
            break
        meta = tick_meta(b, tick_id, n_inputs, frontier, with_hits=False)
        if replay is not None:
            rc = replay.get(tick_id)
        elif controller is not None:
            hint = (source.rate_hint(tick_id)
                    if hasattr(source, "rate_hint") else None)
            if hint is None and len(metrics.records) < 2:
                rc = None     # no rate signal yet (see _decide)
            else:
                rc = controller.observe_live(
                    metrics.snapshot(rate_hint=hint))
        else:
            rc = None
        t0 = time.perf_counter()
        o1, o2, switched, inst_load = pipeline.step_staged(
            b, reconfig=rc, frontier=meta.frontier_before)
        if rc is not None:
            trace.append((tick_id, rc))
            metrics.record_detection(rc.epoch, tick_id, rc, t=t0)
        jax.block_until_ready((o1, o2))        # the synchronous host loop
        sw = bool(np.asarray(switched))
        load = None if inst_load is None else np.asarray(inst_load)
        metrics.record_tick(tick_id, meta.n_tuples,
                            time.perf_counter() - t0, load, 0,
                            n_active=int(active_shadow.sum()))
        if sw:
            switches += 1
            resolved = metrics.record_switch(tick_id)
            if resolved:
                active_shadow = np.asarray(resolved[-1].active).copy()
        sink.accept(tick_id, o1, o2)
    metrics.stop()
    return make_report(metrics, trace, switches), sink
