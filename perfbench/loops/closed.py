"""Closed loop: the pool replayed in order, as fast as the ingest tier
takes ticks, so backpressure paces the source and the generator never
does.  The source stops at the end of the window."""

import time


def rate(traffic, cfg):
    """The event-time rate of the pool (ticks of ``tweets_per_tick`` over
    ``tick_ms``)."""
    return cfg["tweets_per_tick"] * 1000.0 / cfg["tick_ms"]


def ticks(source):
    w = source.window
    i = 0
    while not w.stop.is_set():
        if w.t1 is not None and time.perf_counter() >= w.t1:
            return
        yield source.emit(source.pool_tick(i))
        i += 1
