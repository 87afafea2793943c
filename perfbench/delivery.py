"""Where results leave the system: the benchmark's sink, and which events
each delivery carried.

``DeliverySink`` is what ``build_runtime`` gets as its sink.  ``accept``
(called by the runtime right after each dispatch) only queues the output
handles; a thread of the sink's own fetches them to the host, stamps the
time of delivery and keeps the valid (window, key, count) lanes.  A real
sink has to fetch its results, so the fetch is inside the measurement.
``switches`` times each reconfiguration from its injection to the
delivery of the tick whose epoch switch committed.

``processing_tick`` says which runtime tick processed each event, from the
stream alone: by Definition 3 a ScaleGate releases a tuple once the
watermark -- the least, over sources, of the latest event time seen --
reaches its event time.  The ingest tier's root releases ``tau <= W_j``
after source tick ``j`` (one merged round per source tick), and the
pipeline's own gate, seeing only what the root released, releases
``tau <= W'_j``.  An event is processed in the first tick whose ``W'``
covers it, and delivered when that tick's outputs are fetched.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass
class Delivery:
    tick_id: int            # first runtime tick of the dispatch
    n_ticks: int            # ticks in the dispatch's output stack
    t_accept: float         # host clock when the runtime handed it over
    t: float                # host clock when the fetch completed
    r: np.ndarray           # i64[n] window right boundary (ms)
    key: np.ndarray         # i64[n]
    count: np.ndarray       # f64[n]
    overflow: int           # output-buffer overflow lanes in the dispatch
    switched: Optional[np.ndarray] = None   # bool[n_ticks] epoch switch


class DeliverySink:
    """Sink with a fetch thread of its own (see the module docstring).
    ``decode`` turns one ``Outputs`` stack into (boundary, key, value,
    overflow), as the cell's query defines it."""

    def __init__(self, decode):
        self.decode = decode
        self.deliveries: List[Delivery] = []
        self.error: Optional[BaseException] = None
        self._switched = None
        self._q: "queue.Queue" = queue.Queue()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="bench-sink")
        self._thread.start()

    def note_switched(self, switched) -> None:
        """The per-tick switch flags of the dispatch the runtime hands over
        next (see ``tap_switches``)."""
        self._switched = switched

    def accept(self, tick_id: int, outs_pre, outs_post) -> None:
        sw, self._switched = self._switched, None
        self._q.put((tick_id, time.perf_counter(), outs_pre, outs_post, sw))

    def _loop(self) -> None:
        import jax
        while True:
            item = self._q.get()
            if item is None:
                return
            tick_id, t_accept, o1, o2, sw = item
            try:
                with jax.profiler.TraceAnnotation("bench.sink_fetch"):
                    r1, k1, c1, v1 = self.decode(o1)
                    r2, k2, c2, v2 = self.decode(o2)
                    flags = (None if sw is None
                             else np.atleast_1d(np.asarray(sw)).astype(bool))
                t = time.perf_counter()
                self.deliveries.append(Delivery(
                    tick_id, int(np.asarray(o1.valid).shape[0]), t_accept, t,
                    np.concatenate([r1, r2]), np.concatenate([k1, k2]),
                    np.concatenate([c1, c2]), v1 + v2, flags))
            except BaseException as e:       # surfaced by close()
                self.error = e

    def close(self, timeout: float = 120.0) -> None:
        self._q.put(None)
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise RuntimeError("the sink's fetch thread did not finish")
        if self.error is not None:
            raise self.error


def tap_switches(pipeline, sink: DeliverySink) -> None:
    """Hand the per-tick ``switched`` flags of every step the runtime
    dispatches to the sink, beside the outputs.  The runtime reduces them
    to one flag a dispatch for its own metrics and passes the sink only the
    outputs; the flags are the step's own result, so the harness reads them
    where the step returns them."""
    if hasattr(pipeline, "run_persistent_staged"):
        persistent = pipeline.run_persistent_staged

        def run_persistent_staged(*a, **kw):
            out = persistent(*a, **kw)
            sink.note_switched(out.switched)
            return out
        pipeline.run_persistent_staged = run_persistent_staged
    step = pipeline.step_staged

    def step_staged(*a, **kw):
        out = step(*a, **kw)
        sink.note_switched(out[2])
        return out
    pipeline.step_staged = step_staged


def switches(deliveries: Sequence[Delivery], injected: Sequence[float]
             ) -> List[Tuple[float, float, int]]:
    """For each reconfiguration injected at host time ``injected[i]``:
    (injection time, delivery time of the first tick at or after the
    injection whose epoch switch committed, ticks from the injection to
    it).  The runtime injects a decision into the first tick of the next
    dispatch it hands over, so the injection's tick is the first tick of
    the first delivery accepted after it.  A reconfiguration whose switch
    was never delivered is left out."""
    ds = sorted((d for d in deliveries if d.switched is not None),
                key=lambda d: d.tick_id)
    acc = np.array([d.t_accept for d in ds])
    sw_ticks = np.array([d.tick_id + i for d in ds
                         for i in np.nonzero(d.switched)[0]], np.int64)
    sw_t = np.array([d.t for d in ds for i in np.nonzero(d.switched)[0]])
    out = []
    for t_inj in injected:
        k = int(np.searchsorted(acc, t_inj))
        if k >= len(ds):
            continue
        tick0 = ds[k].tick_id
        j = int(np.searchsorted(sw_ticks, tick0))
        if j < sw_ticks.size:
            out.append((t_inj, float(sw_t[j]), int(sw_ticks[j] - tick0)))
    return out


def watermarks(ticks: Sequence, n_sources: int) -> np.ndarray:
    """``W'_j`` after each source tick ``j`` (see the module docstring)."""
    taus = [t.tau.astype(np.int64) for t in ticks]
    srcs = [t.source for t in ticks]
    front = np.zeros(n_sources, np.int64)        # frontiers start at 0
    w_root = np.empty(len(ticks), np.int64)
    for j, (tau, src) in enumerate(zip(taus, srcs)):
        np.maximum.at(front, src, tau)
        w_root[j] = front.min()
    tau_all = np.concatenate(taus)
    src_all = np.concatenate(srcs)
    order = np.argsort(tau_all, kind="stable")
    tau_s, src_s = tau_all[order], src_all[order]
    out = np.empty(len(ticks), np.int64)
    f = np.zeros(n_sources, np.int64)
    done = 0                               # W_root never falls
    for j, w in enumerate(w_root):
        n = int(np.searchsorted(tau_s, w, side="right"))
        np.maximum.at(f, src_s[done:n], tau_s[done:n])
        done = max(done, n)
        out[j] = f.min()
    return out


def processing_tick(ticks: Sequence, n_sources: int) -> List[np.ndarray]:
    """For each source tick, the runtime tick that processes each of its
    events.  Tick ``len(ticks)`` is the tier's final round, after which the
    root has released everything and ``W'`` is the least, over sources, of
    their last event time; an event beyond it is never processed and gets
    ``len(ticks) + 1``."""
    w = watermarks(ticks, n_sources)
    last = np.full(n_sources, -1, np.int64)
    for t in ticks:
        np.maximum.at(last, t.source, t.tau.astype(np.int64))
    w = np.append(w, last.min())
    return [np.searchsorted(w, t.tau.astype(np.int64), side="left")
            for t in ticks]


def delivery_times(deliveries: Sequence[Delivery], n_ticks: int
                   ) -> np.ndarray:
    """Host time at which each runtime tick's outputs were fetched, the
    final round (``n_ticks``) included; inf for a tick never delivered and
    at ``n_ticks + 1`` (never processed).  A dispatch covers the ticks from
    its first tick id up to the next dispatch's."""
    t = np.full(n_ticks + 2, np.inf)
    ds = sorted(deliveries, key=lambda d: d.tick_id)
    for i, d in enumerate(ds):
        end = ds[i + 1].tick_id if i + 1 < len(ds) else n_ticks + 1
        t[d.tick_id:min(end, n_ticks + 1)] = d.t
    return t
