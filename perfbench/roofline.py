"""The least work each kernel's operation needs, and the chip's peaks.

A kernel's roofline share is its least time over its measured time.  The
least time counts the traffic the *operation* needs, whatever implements
it, so it stays defined and below 100 % when the implementation changes:

* ``segment_aggregate`` -- a scatter-add of the tick's key hits into the
  window-state accumulator: each valid hit read once (an i32 key, an i32
  slot and a float32 value), and each (window, key) cell the tick touches
  read and written once (a float32 count).  The dense one-hot's
  (cell, hit) pairs are not work the operation needs and are not counted.
* ``scalegate_merge`` -- the merge's lane records: each lane's (tau,
  source, valid) read once and its (order, ready) written once.

Both are bounded by memory traffic (they do no arithmetic worth the MXU),
so the least time is bytes over the HBM bandwidth.  The peaks are kept in
``peaks.json``, keyed by ``device_kind``; a device that is missing is an
error, never a default.
"""

from __future__ import annotations

import json
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))

SEGMENT_HIT_BYTES = 4 + 4        # key, slot (int32)
SEGMENT_VALUE_BYTES = 4          # float32
MERGE_LANE_BYTES = 3 * 4 + 2 * 4  # (tau, source, valid) in, (order, ready) out


def peaks(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json")
    return table[device_kind]


def segment_aggregate_bytes(valid_hits: float, touched_cells: float
                            ) -> float:
    return (valid_hits * (SEGMENT_HIT_BYTES + SEGMENT_VALUE_BYTES)
            + touched_cells * 2 * SEGMENT_VALUE_BYTES)


def scalegate_merge_bytes(lanes: float) -> float:
    return lanes * MERGE_LANE_BYTES


def share_pct(least_bytes: float, kernel_s: float,
              pk: Dict[str, float]) -> float:
    """Least time (bytes at peak bandwidth) over kernel time, in %."""
    return 100.0 * (least_bytes / pk["hbm_bytes_per_s"]) / kernel_s
