"""``scalegate_merge``'s share of its roofline, for the pipeline gate's
merge inside the step program: the least time to read each tuple's
(tau, source, valid) once and write its (order, ready) once (see
``perfbench/roofline.py``) over the kernel's device time.  Every device
merges the whole tick (the gate is replicated)."""

from perfbench import roofline
from perfbench.readers import kernel_roofline


def _bytes(run):
    return roofline.scalegate_merge_bytes(run["tuples_per_tick"])


def read(ctx):
    return kernel_roofline(ctx, "scalegate_merge", _bytes, per_block=False)
