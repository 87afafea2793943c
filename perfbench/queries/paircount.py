"""Q1 paircount: pairs of nearby Zipf words of tweets counted per key
over sliding windows (STRETCH sec. 8.1, Appendix D Operator 5).

Everything of the query that the harness does not know in general:

* ``pool`` -- the events: the words of ``repro.data.datagen.tweets``
  (Zipf-distributed, ``% vocab``), and as a tweet's key set every pair of
  words ``(w_i, w_j)`` with ``0 < j - i <= pair_dist``, hashed to a
  virtual key with ``datagen._pair_key``'s hash.  The int64 products of
  that hash wrap, and 2^31 divides 2^64, so the key is the exact product
  mod 2^31; here it is computed in uint64, whose wrap gives the same
  residue.  ``payload[0]`` is the words per tweet.  Each tick's event
  times are uniform over ``tick_ms`` after the previous tick's last one.
* ``reference`` -- the plain reference, a numpy sliding-window count
  independent of ``repro``.  Window ``l`` covers event times
  ``[l*wa, l*wa + ws)``.  Per key it counts the tweets whose key *set*
  holds the key: a pair repeated inside one tweet counts once
  (Definition 4).  It is built one window at a time, as the ticks'
  event times pass each window's end, so it holds only the open windows'
  hits at once.  Rows are keyed by (window right boundary, key), as the
  system emits them.
* ``control`` -- the same counts accumulated one by one in bfloat16, the
  precision below the float32 of the system's counts.
* ``decode`` -- the delivered rows; over a key space beyond float32's
  exact integers the key arrives in two exact parts (see
  ``repro.core.aggregate.reduce_aggregate``).
* ``must_close`` -- the last window the final watermark closes.
* ``work`` -- per tick, what the operation needs (for the rooflines).
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Sequence, Tuple

import numpy as np

from perfbench.generator import Tick

BF16_ONES_CEILING = 256

Rows = Tuple[np.ndarray, np.ndarray, np.ndarray]

PAIR_MUL = 1000003
HASH_MUL = 2654435761


def pair_columns(words_per_tweet: int, pair_dist: int
                 ) -> List[Tuple[int, int]]:
    """The (i, j) word positions of each key column, in datagen's order."""
    return [(i, j) for i in range(words_per_tweet)
            for j in range(i + 1, min(i + 1 + pair_dist, words_per_tweet))]


def pair_key(w1: np.ndarray, w2: np.ndarray, k_virt: int) -> np.ndarray:
    """Word pair -> virtual key: ``((w1 * 1000003 + w2) * 2654435761)``
    mod 2^31 mod ``k_virt``, with the products wrapping mod 2^64."""
    a = w1.astype(np.uint64) * np.uint64(PAIR_MUL) + w2.astype(np.uint64)
    h = (a * np.uint64(HASH_MUL)) % np.uint64(2**31)
    return (h % np.uint64(k_virt)).astype(np.int32)


def draw_keys(rng: np.random.Generator, cfg: Dict, n: int) -> np.ndarray:
    """``n`` tweets of ``words_per_tweet`` Zipf words as pair-key sets."""
    words = rng.zipf(cfg["zipf_s"], (n, cfg["words_per_tweet"])
                     ).astype(np.int64) % cfg["vocab"]
    cols = pair_columns(cfg["words_per_tweet"], cfg["pair_dist"])
    return np.stack([pair_key(words[:, i], words[:, j], cfg["k_virt"])
                     for i, j in cols], axis=1)


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
        pay = np.full((b, 1), float(cfg["words_per_tweet"]), np.float32)
        out.append(Tick(taus, src, keys, pay))
    return out


def key_sets(keys: np.ndarray) -> np.ndarray:
    """Each row's keys sorted, with repeats and padding set to -1."""
    ks = np.sort(keys, axis=1)
    repeat = np.zeros(ks.shape, bool)
    repeat[:, 1:] = ks[:, 1:] == ks[:, :-1]
    return np.where(repeat | (ks < 0), -1, ks)


def window_hits(ticks: Sequence[Tick], wa: int, ws: int
                ) -> Iterator[Tuple[int, np.ndarray]]:
    """(window index, keys) for each window in increasing order: the keys
    of every tweet whose event time falls in the window, one per distinct
    key of the tweet.  A window is yielded once a tick's earliest event
    time has passed its end; the ticks' event times must not go back."""
    n_win = -(-ws // wa)
    open_: Dict[int, List[np.ndarray]] = {}
    last = None
    for t in ticks:
        tau = t.tau.astype(np.int64)
        if tau.size == 0:
            continue
        if last is not None and tau.min() < last:
            raise ValueError("tick event times go back")
        last = int(tau.max())
        for l in sorted(w for w in open_ if w * wa + ws <= tau.min()):
            yield l, np.concatenate(open_.pop(l))
        ks = key_sets(t.keys)
        l_lo = (tau - ws) // wa + 1
        l_hi = tau // wa
        for d in range(n_win):
            l = l_lo + d
            inside = l <= l_hi
            for w in np.unique(l[inside]):
                sel = ks[inside & (l == w)].ravel()
                open_.setdefault(int(w), []).append(sel[sel >= 0])
    for l in sorted(open_):
        yield l, np.concatenate(open_[l])


def reference(ticks: Sequence[Tick], cfg: Dict) -> Rows:
    """-> (right boundary, key, count) arrays, sorted by (boundary, key)."""
    wa, ws = cfg["wa"], cfg["ws"]
    rs, ks, ns = [], [], []
    for l, keys in window_hits(ticks, wa, ws):
        k, n = np.unique(keys, return_counts=True)
        rs.append(np.full(k.size, l * wa + ws, np.int64))
        ks.append(k.astype(np.int64))
        ns.append(n.astype(np.float64))
    if not rs:
        return (np.zeros(0, np.int64), np.zeros(0, np.int64),
                np.zeros(0, np.float64))
    return np.concatenate(rs), np.concatenate(ks), np.concatenate(ns)


def control(want: Rows) -> Rows:
    """The control: the reference's counts summed one at a time in
    bfloat16 (a bfloat16 sum of ones stops at 256)."""
    r, k, n = want
    return r, k, np.minimum(n, BF16_ONES_CEILING).astype(np.float64)


def must_close(watermark: int, cfg: Dict) -> int:
    """The largest window boundary the final watermark closes."""
    wa, ws = cfg["wa"], cfg["ws"]
    return ((watermark - ws) // wa) * wa + ws - wa


def decode(outs) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Valid (right boundary, key, count) lanes of one ``Outputs`` stack,
    and its overflow count; any leading tick/shard axes.  A payload is
    ``[key, count]``, or over a key space beyond float32's exact integers
    ``[key - lo, count, lo]``."""
    valid = np.asarray(outs.valid)
    tau = np.asarray(outs.tau)[valid]
    pay = np.asarray(outs.payload)[valid].astype(np.float64)
    over = int(np.sum(np.asarray(outs.overflow)))
    key = np.rint(pay[:, 0]).astype(np.int64)
    if pay.shape[1] > 2:
        key += np.rint(pay[:, 2]).astype(np.int64)
    return tau.astype(np.int64), key, pay[:, 1], over


def work(ticks: Sequence[Tick], cfg: Dict) -> Dict[str, float]:
    """Per-tick work the operation needs, averaged over the ticks: tuples,
    valid key hits (distinct keys of a tweet x windows its time falls in)
    and distinct (window, key) cells touched."""
    hits, cells = [], []
    for t in ticks:
        per = [k for _, k in window_hits([t], cfg["wa"], cfg["ws"])]
        hits.append(sum(k.size for k in per))
        cells.append(sum(np.unique(k).size for k in per))
    return {"tuples_per_tick": float(np.mean([t.tau.size for t in ticks])),
            "hits_per_tick": float(np.mean(hits)),
            "cells_per_tick": float(np.mean(cells))}
