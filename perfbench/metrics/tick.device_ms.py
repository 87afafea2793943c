"""Device time of the pipeline step program per tick (line
``XLA Modules``, found by its jit name)."""

from perfbench.readers import tick_device_ms


def read(ctx):
    return tick_device_ms(ctx)
