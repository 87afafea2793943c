"""The paircount query's reference count against a brute-force count, its
pair hash against the program's, and the spread of the step's device
time over a four-device trace."""

import collections

import numpy as np
import pytest

from perfbench import spec, trace_reduce

paircount = spec.load_module("queries", "paircount")

CFG = {"zipf_s": 1.3, "words_per_tweet": 6, "pair_dist": 3, "vocab": 30,
       "k_virt": 64, "tweets_per_tick": 48, "tick_ms": 700, "n_sources": 3}


def brute(ticks, wa, ws):
    out = {}
    for t in ticks:
        for tau, ks in zip(t.tau, t.keys):
            for k in set(int(k) for k in ks if k >= 0):
                for l in range((int(tau) - ws) // wa - 2,
                               int(tau) // wa + 3):
                    if l * wa <= tau < l * wa + ws:
                        r = l * wa + ws
                        out[(r, k)] = out.get((r, k), 0) + 1
    return out


def test_counts_match_brute_force():
    ticks = paircount.pool(np.random.default_rng(5), CFG, 5)
    assert ticks[0].keys.shape == (48, 12)
    # a tweet whose words repeat a pair: (3, 4) at columns (0, 1) and
    # (2, 3) of the word list gives one key twice in its set
    words = np.array([[3, 4, 3, 4, 9, 9]])
    cols = paircount.pair_columns(6, 3)
    ticks[1].keys[7] = [paircount.pair_key(words[:, i], words[:, j],
                                           CFG["k_virt"])[0]
                        for i, j in cols]
    assert len(set(ticks[1].keys[7].tolist())) < 12
    ticks[0].keys[:5, 8:] = -1                   # padded key sets
    for wa, ws in ((1000, 2000), (500, 1500), (1000, 1000), (3000, 9000)):
        r, k, n = paircount.reference(ticks, dict(CFG, wa=wa, ws=ws))
        got = {(int(a), int(b)): int(c) for a, b, c in zip(r, k, n)}
        assert got == brute(ticks, wa, ws)
        order = list(zip(r, k))
        assert order == sorted(order)


def test_pair_keys_are_the_programs_at_the_top_word_ids():
    """``pair_key`` is ``repro.data.datagen._pair_key`` (whose int64
    products wrap) for the deployment's largest words and key space."""
    from repro.data import datagen
    cfg = spec.load_cell("q1-paircount-mesh4.saturated").config
    top = np.arange(cfg["vocab"] - 6, cfg["vocab"], dtype=np.int64)
    w1, w2 = np.meshgrid(np.concatenate([top, [0, 1, 2**20]]),
                         np.concatenate([top, [0, 7]]))
    for k_virt in (cfg["k_virt"], 4096):
        np.testing.assert_array_equal(
            paircount.pair_key(w1.ravel(), w2.ravel(), k_virt),
            datagen._pair_key(w1.ravel(), w2.ravel(), k_virt))


def test_work_is_the_reference_counts_of_each_tick():
    cfg = dict(CFG, wa=1000, ws=2000)
    ticks = paircount.pool(np.random.default_rng(7), cfg, 4)
    sizes = paircount.work(ticks, cfg)
    per = [paircount.reference([t], cfg) for t in ticks]
    assert sizes["hits_per_tick"] == np.mean([n.sum() for _, _, n in per])
    assert sizes["cells_per_tick"] == np.mean([n.size for _, _, n in per])
    assert sizes["tuples_per_tick"] == 48


Ev = collections.namedtuple("Ev", "name start_ns end_ns duration_ns")
Line = collections.namedtuple("Line", "name events")
Plane = collections.namedtuple("Plane", "name lines stats")
Profile = collections.namedtuple("Profile", "planes")


def four_device_profile(step_ns):
    """Two runs of the step program on each device, of the given lengths."""
    planes = [Plane("Task Environment", [], [("profile_start_time", 0),
                                             ("profile_stop_time", 10**6)])]
    for d, ns in enumerate(step_ns):
        mods = [Ev(f"jit__persistent_fn({d})", s, s + ns, ns)
                for s in (1000, 500_000)]
        ops = [Ev("fusion.1", s, s + ns, ns) for s in (1000, 500_000)]
        planes.append(Plane(f"/device:TPU:{d}",
                            [Line("XLA Ops", ops),
                             Line("XLA Modules", mods)], []))
    return Profile(planes)


def test_shard_spread_on_a_four_device_trace():
    read = spec.reader("tick.shard_spread")
    tr = trace_reduce.reduce(four_device_profile([900, 1000, 1100, 1000]))
    ctx = {"trace": tr, "run": {"step_module": r"jit__persistent_fn",
                                "ticks_per_run": 8}}
    assert read(ctx) == pytest.approx(100.0 * 200 / 1000)
    even = trace_reduce.reduce(four_device_profile([1000] * 4))
    assert read(dict(ctx, trace=even)) == 0.0
    one = trace_reduce.reduce(four_device_profile([1000]))
    assert read(dict(ctx, trace=one)) is None
    assert read(dict(ctx, trace=None)) is None


TINY_PAIRS = {
    "name": "tiny-pairs", "query": "paircount", "vocab": 4096,
    "zipf_s": 1.3, "words_per_tweet": 6, "pair_dist": 3,
    "tweets_per_tick": 128, "tick_ms": 1437, "op": "count", "wa": 3000,
    "ws": 9000, "k_virt": 8192, "out_cap": 2048, "extra_slots": 2,
    "n_max": 8, "n_active": 8, "stash_cap": 64, "n_sources": 4,
    "ingest_hosts": 2, "ingest_worker": "thread", "leaf_cap": 128,
    "root_cap": 128, "out_pad": 128, "root_device": True, "super_batch": 2,
    "queue_cap": 2, "chan_cap": 2,
}


def test_traced_paircount_run_on_four_devices(tmp_path, monkeypatch):
    """The query through the whole harness on four virtual devices: exact,
    and the span of the host's key histogram read from the run (the CPU's
    trace has no TPU device planes, so the spread is left out here)."""
    import time

    from conftest import make_root

    from perfbench import roofline, run
    monkeypatch.setattr(roofline, "peaks",
                        lambda kind: {"hbm_bytes_per_s": 1e11})
    root = make_root(str(tmp_path), config=TINY_PAIRS, mesh=4)
    res = run.run_cell(spec.load_cell("tiny.closed", root), 2**33 + 29,
                       3.0, True, time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0
    m = res["metrics"]
    assert m["ingest.key_hist_ms"]["value"] > 0
    assert "tick.shard_spread" in res["run"]["metrics_missing"]
    assert res["device"]["count"] == 4
