"""Mean host time to stage one dispatch onto the device (span
``ingest.stage``, ``repro/core/async_runtime.py``) inside the window."""

from perfbench.readers import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "ingest.stage")
