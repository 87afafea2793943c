"""Mean time the step loop waits for ingest's next staged dispatch (span
``runtime.wait``, ``repro/core/async_runtime.py``) inside the window."""

from perfbench.readers import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "runtime.wait")
