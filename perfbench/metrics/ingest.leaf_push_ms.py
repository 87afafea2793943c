"""Mean host time of one ingest leaf's push (span ``leaf.push``,
``repro/ingest/leaf.py``) inside the window."""

from perfbench.readers import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "leaf.push")
