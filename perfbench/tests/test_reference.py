"""The wordcount query's reference count against a brute-force count,
and the control's bfloat16 arithmetic against bfloat16 itself."""

import itertools

import numpy as np

from perfbench import spec

wordcount = spec.load_module("queries", "wordcount")


def brute(taus, keys, wa, ws):
    out = {}
    for tau, ks in zip(np.concatenate(taus), np.concatenate(keys)):
        for k in set(int(k) for k in ks if k >= 0):
            for l in range((int(tau) - ws) // wa - 2, int(tau) // wa + 3):
                if l * wa <= tau < l * wa + ws:
                    r = l * wa + ws
                    out[(r, k)] = out.get((r, k), 0) + 1
    return out


def test_counts_match_brute_force():
    cfg = {"zipf_s": 1.3, "words_per_tweet": 6, "vocab": 40, "k_virt": 16,
           "tweets_per_tick": 64, "tick_ms": 700, "n_sources": 3}
    ticks = wordcount.pool(np.random.default_rng(5), cfg, 5)
    ticks[0].keys[:5, 3:] = -1                   # padded key sets
    taus, keys = [t.tau for t in ticks], [t.keys for t in ticks]
    for wa, ws in ((1000, 2000), (500, 1500), (1000, 1000), (3000, 9000)):
        r, k, n = wordcount.reference(ticks, dict(cfg, wa=wa, ws=ws))
        got = {(int(a), int(b)): int(c) for a, b, c in zip(r, k, n)}
        assert got == brute(taus, keys, wa, ws)
        order = list(zip(r, k))
        assert order == sorted(order)


def test_bf16_control_is_a_bfloat16_sum_of_ones():
    import jax.numpy as jnp
    acc = jnp.zeros((), jnp.bfloat16)
    seen = []
    for _ in range(600):
        acc = acc + jnp.ones((), jnp.bfloat16)
        seen.append(float(acc))
    n = np.arange(1, 601, dtype=np.float64)
    _, _, got = wordcount.control((n, n, n))
    assert np.array_equal(got, np.array(seen))
    assert list(itertools.islice((c for c in seen if c < 256), 3)) == [
        1.0, 2.0, 3.0]


def test_work_is_the_reference_counts_of_each_tick():
    cfg = {"zipf_s": 1.3, "words_per_tweet": 6, "vocab": 40, "k_virt": 16,
           "tweets_per_tick": 64, "tick_ms": 700, "n_sources": 3,
           "wa": 1000, "ws": 2000}
    ticks = wordcount.pool(np.random.default_rng(7), cfg, 4)
    sizes = wordcount.work(ticks, cfg)
    per = [wordcount.reference([t], cfg) for t in ticks]
    assert sizes["hits_per_tick"] == np.mean([n.sum() for _, _, n in per])
    assert sizes["cells_per_tick"] == np.mean([n.size for _, _, n in per])
    assert sizes["tuples_per_tick"] == 64
