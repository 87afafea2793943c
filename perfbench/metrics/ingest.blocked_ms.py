"""Mean time the ingest thread waits to hand one staged dispatch to the
step loop, under backpressure (span ``ingest.blocked``,
``repro/core/async_runtime.py``) inside the window."""

from perfbench.readers import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "ingest.blocked")
