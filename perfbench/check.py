"""The comparison that decides ``correct``.

Every (window, key) count the sink delivered, for every window the run
closed, against the reference count of the same generated events.  A
window is closed once the system has emitted it; windows close in order of
their right boundary, so the closed set is every window up to the largest
boundary delivered.  That boundary may not fall short of the last one the
stream's watermark closes (``must_close``), so a run that stops emitting
early is missing windows, not excused from them.

The numbers compared, each with limit 0 (the comparison is exact):

* ``missing``  -- reference (window, key) counts never delivered;
* ``extra``    -- delivered counts the reference does not have, or a
  (window, key) delivered twice;
* ``wrong``    -- delivered counts that differ from the reference;
* ``overflow`` -- lanes dropped by any bounded buffer on the path (output
  buffers, pipeline stash, window ring, ingest tier), as the program
  counts them.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

LIMITS = {"missing": 0, "extra": 0, "wrong": 0, "overflow": 0}


def _codes(r: np.ndarray, key: np.ndarray) -> np.ndarray:
    return r.astype(np.int64) * (1 << 32) + key.astype(np.int64)


def compare(got: Tuple[np.ndarray, np.ndarray, np.ndarray],
            want: Tuple[np.ndarray, np.ndarray, np.ndarray],
            must_close: int, overflow: int) -> Dict[str, int]:
    """``got``/``want`` are (right boundary, key, count) arrays."""
    g_r, g_k, g_c = got
    w_r, w_k, w_c = want
    closed = max(int(g_r.max()) if g_r.size else -1, must_close)
    keep = w_r <= closed
    w_code, w_c = _codes(w_r[keep], w_k[keep]), w_c[keep]
    g_code = _codes(g_r, g_k)
    order = np.argsort(g_code, kind="stable")
    g_code, g_c = g_code[order], g_c[order]
    dup = int(np.sum(g_code[1:] == g_code[:-1]))
    u_code, first = np.unique(g_code, return_index=True)
    u_c = g_c[first]
    pos = np.searchsorted(u_code, w_code)
    pos_c = np.minimum(pos, max(u_code.size - 1, 0))
    found = (pos < u_code.size) & (u_code[pos_c] == w_code) \
        if u_code.size else np.zeros(w_code.shape, bool)
    wrong = int(np.sum(found & (u_c[pos_c] != w_c))) if u_code.size else 0
    in_want = np.isin(u_code, w_code)
    return {"missing": int(np.sum(~found)),
            "extra": int(np.sum(~in_want)) + dup,
            "wrong": wrong,
            "overflow": int(overflow),
            "compared": int(keep.sum()),
            "closed_windows": int(np.unique(w_r[keep]).size)}


def verdict(numbers: Dict[str, int]) -> bool:
    return all(numbers[k] <= lim for k, lim in LIMITS.items())


def limits(numbers: Dict[str, int]) -> Dict[str, Dict[str, int]]:
    """Each number compared beside its limit."""
    return {k: {"value": numbers[k], "limit": lim}
            for k, lim in LIMITS.items()}
