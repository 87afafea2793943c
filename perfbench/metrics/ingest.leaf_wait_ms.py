"""Mean host time one ingest leaf's push waits to read its results back
from the device (span ``leaf.fetch``, ``repro/ingest/leaf.py``) inside
the window: ``leaf.push`` less this is the leaf's own work."""

from perfbench.readers import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "leaf.fetch")
