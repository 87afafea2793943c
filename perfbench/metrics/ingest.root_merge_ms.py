"""Mean host time of one root merge round (span ``root.merge``,
``repro/ingest/tier.py`` around ``repro/ingest/root.py``) inside the
window."""

from perfbench.readers import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "root.merge")
