"""Q1 wordcount: Zipf words of tweets counted per key over sliding windows.

Everything of the query that the harness does not know in general:

* ``pool`` -- the events: a copy of ``repro.data.datagen.tweets``
  (wordcount mode) that returns plain numpy arrays, since the yardstick
  may not change when the program does.  Words are Zipf-distributed,
  ``% vocab``, and hashed to virtual keys with the same multiplicative
  hash; ``payload[0]`` is the words per tweet.  Each tick's event times
  are uniform over ``tick_ms`` after the previous tick's last one.
* ``reference`` -- the plain reference, a numpy sliding-window count
  independent of ``repro`` (copied from ``chip_smoke.reference_counts``).
  Window ``l`` covers event times ``[l*wa, l*wa + ws)``.  Per key it
  counts the tweets whose key set holds the key; a key repeated inside one
  tweet counts once.  Rows are keyed by (window right boundary, key), as
  the system emits them.
* ``control`` -- the same counts accumulated one by one in bfloat16, the
  precision below the float32 of the system's counts.  A bfloat16 sum of
  ones stops at 256 (256 + 1 rounds back to 256).
* ``decode`` -- a delivered ``Outputs`` stack as (boundary, key, count)
  rows.
* ``work`` -- per tick, what the operation needs (for the rooflines).
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from perfbench.generator import Tick

BF16_ONES_CEILING = 256

Rows = Tuple[np.ndarray, np.ndarray, np.ndarray]


def key_of(words: np.ndarray, k_virt: int) -> np.ndarray:
    """Word -> virtual key (``repro.data.datagen._key_of``)."""
    return (words * 2654435761 % 2**31 % k_virt).astype(np.int32)


def draw_keys(rng: np.random.Generator, cfg: Dict, n: int) -> np.ndarray:
    """``n`` tweets of ``words_per_tweet`` Zipf words as virtual keys."""
    words = rng.zipf(cfg["zipf_s"], (n, cfg["words_per_tweet"])
                     ).astype(np.int64) % cfg["vocab"]
    return key_of(words, cfg["k_virt"])


def pool(rng: np.random.Generator, cfg: Dict, n_ticks: int,
         tau0: int = 0) -> List[Tick]:
    """``n_ticks`` consecutive ticks of ``tweets_per_tick`` tweets."""
    out, tau = [], tau0
    b = cfg["tweets_per_tick"]
    for _ in range(n_ticks):
        taus = np.sort(tau + rng.integers(0, cfg["tick_ms"], b)
                       ).astype(np.int32)
        tau = int(taus.max()) + 1
        keys = draw_keys(rng, cfg, b)
        src = rng.integers(0, cfg["n_sources"], b).astype(np.int32)
        pay = np.full((b, 1), float(keys.shape[1]), np.float32)
        out.append(Tick(taus, src, keys, pay))
    return out


def _hits(ticks: Sequence[Tick], wa: int, ws: int):
    """(window index, key) of every hit: each distinct key of a tweet in
    each window its event time falls in."""
    tau = np.concatenate([t.tau for t in ticks]).astype(np.int64)
    ks = np.sort(np.concatenate([t.keys for t in ticks]), axis=1)
    first = np.ones(ks.shape, bool)
    first[:, 1:] = ks[:, 1:] != ks[:, :-1]
    use = (ks >= 0) & first
    l_lo = (tau - ws) // wa + 1
    l_hi = tau // wa
    wins, kk = [], []
    for d in range(-(-ws // wa)):
        l = l_lo + d
        m = use & (l <= l_hi)[:, None]
        wins.append(np.broadcast_to(l[:, None], ks.shape)[m])
        kk.append(ks[m].astype(np.int64))
    return np.concatenate(wins), np.concatenate(kk)


def reference(ticks: Sequence[Tick], cfg: Dict) -> Rows:
    """-> (right boundary, key, count) arrays, sorted by (boundary, key)."""
    wa, ws = cfg["wa"], cfg["ws"]
    wins, kk = _hits(ticks, wa, ws)
    base = wins.min()
    code, n = np.unique((wins - base) * (1 << 32) + kk, return_counts=True)
    right = ((code >> 32) + base) * wa + ws
    return right, code & 0xFFFFFFFF, n.astype(np.float64)


def control(want: Rows) -> Rows:
    """The control: the reference's counts summed one at a time in
    bfloat16."""
    r, k, n = want
    return r, k, np.minimum(n, BF16_ONES_CEILING).astype(np.float64)


def must_close(watermark: int, cfg: Dict) -> int:
    """The largest window boundary the final watermark closes."""
    wa, ws = cfg["wa"], cfg["ws"]
    return ((watermark - ws) // wa) * wa + ws - wa


def decode(outs) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Valid (right boundary, key, count) lanes of one ``Outputs`` stack,
    and its overflow count; any leading tick/shard axes."""
    valid = np.asarray(outs.valid)
    tau = np.asarray(outs.tau)[valid]
    pay = np.asarray(outs.payload)[valid].astype(np.float64)
    over = int(np.sum(np.asarray(outs.overflow)))
    return (tau.astype(np.int64), np.rint(pay[:, 0]).astype(np.int64),
            pay[:, 1], over)


def work(ticks: Sequence[Tick], cfg: Dict) -> Dict[str, float]:
    """Per-tick work the operation needs, averaged over the ticks: tuples,
    valid key hits (distinct keys of a tweet x windows its time falls in)
    and distinct (window, key) cells touched."""
    hits, cells = [], []
    for t in ticks:
        wins, kk = _hits([t], cfg["wa"], cfg["ws"])
        hits.append(wins.size)
        cells.append(np.unique(wins * (1 << 32) + kk).size)
    return {"tuples_per_tick": float(np.mean([t.tau.size for t in ticks])),
            "hits_per_tick": float(np.mean(hits)),
            "cells_per_tick": float(np.mean(cells))}
