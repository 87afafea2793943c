"""Sink adapters: where the pipeline's per-tick ``Outputs`` land.

Sinks keep *device handles* — accepting an output never forces a host
sync (that would serialize the async loop); materialization happens in
``results()``/``finalize()`` after the run.  ``CollectSink`` is the parity
workhorse (sorted (tau, payload) multiset, the repo-wide output-set
equality currency); ``NullSink`` is the throughput-bench sink.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np


def flatten_outputs(outs) -> List[Tuple[int, tuple]]:
    """(tau, rounded payload tuple) for every valid lane; handles both flat
    and per-instance / per-shard stacked Outputs (any leading dims)."""
    tau = np.asarray(outs.tau).reshape(-1)
    val = np.asarray(outs.valid).reshape(-1)
    pay = np.asarray(outs.payload)
    # round in float64: numpy rounds a float32 by scaling it by 10**4 in
    # float32, which moves any value above ~1678 (26869 -> 26868.998)
    pay = np.round(pay.reshape(-1, pay.shape[-1])[val].astype(np.float64), 4)
    return [(int(t), tuple(p)) for t, p in zip(tau[val], pay)]


class CollectSink:
    """Retains every tick's output handles; ``results()`` materializes the
    sorted output multiset."""

    def __init__(self):
        self._held = []            # (tick_id, outs_pre, outs_post)
        self.ticks = 0

    def accept(self, tick_id: int, outs_pre, outs_post) -> None:
        self._held.append((tick_id, outs_pre, outs_post))
        self.ticks += 1

    def results(self, *, before_tick: Optional[int] = None,
                since_tick: Optional[int] = None) -> List[Tuple[int, tuple]]:
        """Sorted output multiset, optionally restricted to a tick-id range
        (``since_tick <= tick_id < before_tick``).  The recovery drills use
        ``before_tick=S`` to keep only the outputs *committed* before the
        restored snapshot's step S — outputs at or past S are regenerated by
        replay, and counting both would double-deliver."""
        res: List[Tuple[int, tuple]] = []
        for tick_id, o1, o2 in self._held:
            if before_tick is not None and tick_id >= before_tick:
                continue
            if since_tick is not None and tick_id < since_tick:
                continue
            res += flatten_outputs(o1) + flatten_outputs(o2)
        return sorted(res)


class NullSink:
    """Drops outputs (keeps only the latest handle so the final
    ``finalize()`` can fence the device queue) — the throughput sink."""

    def __init__(self):
        self.ticks = 0
        self._last = None

    def accept(self, tick_id: int, outs_pre, outs_post) -> None:
        self.ticks += 1
        self._last = outs_pre

    def finalize(self) -> None:
        if self._last is not None:
            np.asarray(self._last.tau)

    def results(self) -> Optional[list]:
        return None
