"""Mean time from the controller's decision to the return of the
runtime's drain of the dispatch handed over before the reconfiguration's
own: the device work queued ahead of it (span ``reconfig.behind``,
``repro/core/async_runtime.py``), inside the window."""

from perfbench.readers import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "reconfig.behind")
