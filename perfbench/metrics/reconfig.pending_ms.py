"""Mean time from the controller's decision to the runtime's drain that
observes the reconfiguration's switch (span ``reconfig.pending``,
``repro/io/metrics.py``), over the switches observed inside the window."""

from perfbench.readers import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "reconfig.pending")
