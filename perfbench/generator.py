"""The one traffic generator: a query's events, offered by a loop.

A traffic mix is a JSON file under ``perfbench/traffic/`` (see
``spec.load_cell``); nothing but its parameters and the configuration's
sizes decides what the stream looks like.  The configuration's ``query``
module makes a pool of ``pool_ticks`` distinct ticks from the seed, in
set-up; the traffic's ``loop`` module (``perfbench/loops/<loop>.py``)
offers them to the system:

* ``loop.rate(traffic, cfg)`` -- the offered rate in events per second,
  handed to the ingest tier as a constant rate hint (so a scripted
  controller is consulted from the first dispatch on; the scripted
  controllers ignore the rate itself);
* ``loop.ticks(source)`` -- yields the ticks, each through
  ``source.emit``, which records it: the reference and the delivery
  attribution read that record.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Tick:
    """One source tick as numpy arrays, tau-sorted."""
    tau: np.ndarray        # i32[B] event time, ms
    source: np.ndarray     # i32[B]
    keys: np.ndarray       # i32[B, kmax] the tuple's key set, -1 padded
    payload: np.ndarray    # f32[B, P]

    def retimed(self, tau: np.ndarray) -> "Tick":
        return Tick(tau.astype(np.int32), self.source, self.keys,
                    self.payload)

    def batch(self):
        """The ``TupleBatch`` the system ingests."""
        from repro.core import tuples as T
        b = self.tau.shape[0]
        return T.TupleBatch(
            tau=self.tau, keys=self.keys, payload=self.payload,
            source=self.source, valid=np.ones((b,), bool),
            is_control=np.zeros((b,), bool),
            ctrl_epoch=np.zeros((b,), np.int32))


class Window:
    """The measured window, shared by the harness and the source thread."""

    def __init__(self):
        self.t1: Optional[float] = None
        self.stop = threading.Event()     # the harness aborts the source

    def open(self, t0: float, seconds: float) -> None:
        self.t1 = t0 + seconds


class Source:
    """Iterable of ``TupleBatch`` ticks for ``repro.api.build_runtime``."""

    def __init__(self, query, loop, traffic: Dict, cfg: Dict, seed: int,
                 window: Window):
        from repro.io.sources import RateSchedule
        self.traffic, self.cfg, self.window = traffic, cfg, window
        self.loop = loop
        self.pool = query.pool(np.random.default_rng(seed), cfg,
                               traffic["pool_ticks"])
        self.span = int(self.pool[-1].tau.max()) + 1
        self.rate = float(loop.rate(traffic, cfg))
        self.schedule = RateSchedule(((1, self.rate),))
        self.emitted: List[Tick] = []

    def __iter__(self):
        return self.loop.ticks(self)

    def pool_tick(self, i: int) -> Tick:
        """Tick ``i`` of the pool replayed cycle after cycle, event times
        shifted forward by the pool's span each cycle."""
        cycle, k = divmod(i, len(self.pool))
        t = self.pool[k]
        return t.retimed(t.tau + np.int32(cycle * self.span))

    def emit(self, tick: Tick):
        self.emitted.append(tick)
        return tick.batch()
