"""A plain sliding-window count, independent of the pipeline: the
reference the paircount tests compare the system with.

Window ``l`` covers event times ``[l*wa, l*wa + ws)`` and is keyed by its
right boundary ``l*wa + ws``.  Per (window, key) it counts the tuples
whose key *set* holds the key: a key repeated inside one tuple counts
once (Definition 4), and negative keys are padding.
"""

from typing import Dict, Tuple

import numpy as np


def window_counts(tau: np.ndarray, keys: np.ndarray, wa: int, ws: int
                  ) -> Dict[Tuple[int, int], int]:
    """``tau`` i[B] event times, ``keys`` i[B, KMAX] key sets ->
    {(right boundary, key): count}."""
    tau = np.asarray(tau, np.int64)
    ks = np.sort(np.asarray(keys, np.int64), axis=1)
    first = np.ones(ks.shape, bool)
    first[:, 1:] = ks[:, 1:] != ks[:, :-1]
    use = first & (ks >= 0)
    l_lo = (tau - ws) // wa + 1
    l_hi = tau // wa
    wins, kk = [], []
    for d in range(-(-ws // wa)):
        l = l_lo + d
        m = use & (l <= l_hi)[:, None]
        wins.append(np.broadcast_to(l[:, None], ks.shape)[m])
        kk.append(ks[m])
    right = np.concatenate(wins) * wa + ws
    cells, n = np.unique(np.stack([right, np.concatenate(kk)], 1),
                         axis=0, return_counts=True)
    return {(int(r), int(k)): int(c) for (r, k), c in zip(cells, n)}
