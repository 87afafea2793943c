"""``segment_aggregate``'s share of its roofline: the least time of a
scatter-add of the tick's valid key hits into the window state (hits read
once, touched (window, key) cells read and written once; see
``perfbench/roofline.py``) over the kernel's device time."""

from perfbench import roofline
from perfbench.readers import kernel_roofline


def _bytes(run):
    return roofline.segment_aggregate_bytes(run["hits_per_tick"],
                                            run["cells_per_tick"])


def read(ctx):
    return kernel_roofline(ctx, "segment_aggregate", _bytes, per_block=True)
