"""Mean host time of one tick's key histogram, the input of the
per-instance load where the step returns none (span ``ingest.key_hist``,
``repro/core/async_runtime.py``) inside the window."""

from perfbench.readers import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "ingest.key_hist")
