"""The attribution of events to runtime ticks, and delivery times."""

import numpy as np

from perfbench import delivery, spec

wordcount = spec.load_module("queries", "wordcount")


def gate_model(ticks, n_sources):
    """Step by step: a root gate over all sources, then a second gate over
    what the first released (tuples keep their source ids)."""
    stash1, stash2 = [], []
    f1 = np.zeros(n_sources, int)
    f2 = np.zeros(n_sources, int)
    done = []
    for t in ticks:
        for tau, s in zip(t.tau, t.source):
            stash1.append((int(tau), int(s)))
            f1[s] = max(f1[s], tau)
        w1 = f1.min()
        rel = [x for x in stash1 if x[0] <= w1]
        stash1 = [x for x in stash1 if x[0] > w1]
        for tau, s in rel:
            stash2.append((tau, s))
            f2[s] = max(f2[s], tau)
        w2 = f2.min()
        done.append(sorted(x for x in stash2 if x[0] <= w2))
        stash2 = [x for x in stash2 if x[0] > w2]
    return done


def test_processing_tick_matches_two_gates():
    cfg = {"zipf_s": 1.3, "words_per_tweet": 2, "vocab": 10, "k_virt": 8,
           "tweets_per_tick": 40, "tick_ms": 30, "n_sources": 3}
    ticks = wordcount.pool(np.random.default_rng(3), cfg, 6)
    proc = delivery.processing_tick(ticks, 3)
    want = gate_model(ticks, 3)
    for j, rel in enumerate(want):
        got = sorted((int(t.tau[i]), int(t.source[i]))
                     for t, p in zip(ticks, proc)
                     for i in np.nonzero(p == j)[0])
        assert got == rel
    assert all(p.min() >= 0 and p.max() <= len(ticks) + 1 for p in proc)


def test_delivery_times_cover_dispatch_ranges():
    D = delivery.Delivery
    z = np.zeros(0)
    ds = [D(4, 4, 1.5, 2.0, z, z, z, 0), D(0, 4, 0.5, 1.0, z, z, z, 0),
          D(8, 4, 2.5, 3.0, z, z, z, 0)]
    t = delivery.delivery_times(ds, 9)
    assert list(t[:10]) == [1.0] * 4 + [2.0] * 4 + [3.0] * 2
    assert np.isinf(t[10])


def test_switches_time_injection_to_the_delivered_switch():
    """Dispatches of 4 ticks accepted at 0.5, 1.5, 2.5, 3.5 and delivered
    half a second later.  Injected before the first: switched in its tick
    2.  Before the second: switched only in the third dispatch's tick 0
    (a slip of 4 ticks).  Before the fourth: never switched."""
    D = delivery.Delivery
    z = np.zeros(0)
    f = np.zeros(4, bool)
    ds = [D(0, 4, 0.5, 1.0, z, z, z, 0, np.array([0, 0, 1, 0], bool)),
          D(4, 4, 1.5, 2.0, z, z, z, 0, f),
          D(8, 4, 2.5, 3.0, z, z, z, 0, np.array([1, 0, 0, 0], bool)),
          D(12, 4, 3.5, 4.0, z, z, z, 0, f)]
    got = delivery.switches(ds, [0.4, 1.4, 3.4])
    assert got == [(0.4, 1.0, 2), (1.4, 3.0, 4)]
