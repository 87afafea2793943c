"""Hierarchical multi-host ScaleGate (repro.ingest) — ISSUE-4 acceptance.

The contracts under test:
  * exact output-set parity between N-leaf hierarchical ingest and the
    single-ScaleGate oracle, on q1-style aggregation and q3-style join
    streams — per round while membership is static, as a multiset across a
    mid-stream ``add_host``/``remove_host`` (the reconfig rounds shift tick
    boundaries but never the content);
  * the merged ready stream stays totally ordered and the root watermark
    never regresses (RootMerge additionally asserts both on every round);
  * membership changes move zero tuple state and report attach/detach
    latency;
  * backpressure: a slow tier consumer stalls the source iterator through
    the bounded channels;
  * stash overflow is counted and surfaced (warning + stats) at both the
    leaf and root levels, including under a mid-stream remove_host flush;
  * the ``merge_order`` tie-break contract is explicit per backend, the
    two contracts agree on everything but the tie order, and the root
    merge tolerates either.
"""

import os
import threading
import time
import warnings

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import scalegate, tuples as T
from repro.core import watermark as wm
from repro.data import datagen
from repro.ingest import (IngestTier, SourcePartitioner, collect_tuples,
                          emitted_taus, single_gate_stream)

K = 64
N_SRC = 4


def agg_stream(n_ticks=6, seed=0, tick=16, n_sources=N_SRC):
    """q1-style: multi-key aggregation tuples spread over n_sources."""
    rng = np.random.default_rng(seed)
    return list(datagen.tweets(rng, n_ticks=n_ticks, tick=tick,
                               words_per_tweet=3, vocab=300, k_virt=K,
                               rate_per_tick=30, n_sources=n_sources))


def join_stream(n_ticks=5, seed=3, tick=16):
    """q3-style: the two-stream band-join workload (source = L/R)."""
    rng = np.random.default_rng(seed)
    return list(datagen.scalejoin(rng, n_ticks=n_ticks, tick=tick, k_virt=1))


def tier_kw(**over):
    kw = dict(worker="thread", leaf_cap=32, root_cap=64)
    kw.update(over)
    return kw


def assert_ordered(outs):
    taus = emitted_taus(outs)
    assert (np.diff(taus) >= 0).all(), "ready stream lost total order"


# ----------------------------------------------------------- parity -------

@pytest.mark.parametrize("worker", ["inline", "thread"])
@pytest.mark.parametrize("n_leaves", [1, 2, 3])
def test_parity_q1_style(worker, n_leaves):
    batches = agg_stream()
    tier = IngestTier(batches, N_SRC, n_leaves, **tier_kw(worker=worker))
    outs = list(tier)
    assert_ordered(outs)
    oracle = single_gate_stream(batches, N_SRC, cap=96)
    # static membership: the tier is round-for-tick exact, not just a
    # multiset — every data round emits exactly the oracle's ready set
    assert len(outs) == len(oracle)           # n_ticks + final flush
    for got, want in zip(outs, oracle):
        assert collect_tuples([got]) == collect_tuples([want])
    st = tier.stats()
    assert st.tuples_out == st.tuples_in
    assert st.total_overflow == 0


def test_parity_q3_style_join_stream():
    batches = join_stream()
    tier = IngestTier(batches, 2, 2, **tier_kw())
    outs = list(tier)
    assert_ordered(outs)
    oracle = single_gate_stream(batches, 2, cap=96)
    for got, want in zip(outs, oracle):
        assert collect_tuples([got]) == collect_tuples([want])
    assert tier.stats().tuples_out > 0


def test_parity_across_add_and_remove_host():
    """Hosts join and leave mid-stream: the output multiset still exactly
    equals the flat oracle, order and watermark monotonicity hold (the
    root asserts them every round), and both membership latencies are
    measured."""
    batches = agg_stream(n_ticks=8)
    tier = IngestTier(batches, N_SRC, 2, **tier_kw())
    new_leaf = tier.add_host(at_tick=2)
    tier.remove_host(0, at_tick=5)
    outs = list(tier)
    assert_ordered(outs)
    oracle = single_gate_stream(batches, N_SRC, cap=96)
    assert collect_tuples(outs) == collect_tuples(oracle)
    st = tier.stats()
    assert st.tuples_out == st.tuples_in
    assert 0 not in st.leaves and new_leaf in st.leaves
    assert len(st.attach_ms) == 1 and len(st.detach_ms) == 1
    assert st.attach_ms[0] >= 0 and st.detach_ms[0] >= 0


def test_parity_join_stream_across_membership_change():
    batches = join_stream(n_ticks=7)
    tier = IngestTier(batches, 2, 1, **tier_kw())
    tier.add_host(at_tick=2)                  # 1 -> 2 leaves mid-stream
    outs = list(tier)
    assert_ordered(outs)
    oracle = single_gate_stream(batches, 2, cap=96)
    assert collect_tuples(outs) == collect_tuples(oracle)


def test_process_workers_parity():
    """Leaf workers as real spawned processes (one per ingest host)."""
    batches = agg_stream(n_ticks=3)
    tier = IngestTier(batches, N_SRC, 2, **tier_kw(worker="process"))
    outs = list(tier)
    assert_ordered(outs)
    oracle = single_gate_stream(batches, N_SRC, cap=96)
    assert collect_tuples(outs) == collect_tuples(oracle)


def test_process_leaf_runs_on_host_cpu(monkeypatch):
    """A spawned leaf pins itself to the CPU and the xla backend, whatever
    the parent's environment asks for: on a TPU machine the chip belongs
    to the parent process alone.  The children inherit
    ``REPRO_KERNEL_BACKEND=pallas`` (the compiled kernels, which cannot run
    on a CPU); the parent's own root merge runs xla by argument.  The
    leaves' ``leaf_start`` events, shipped back on the round stream, report
    what they actually ran."""
    from repro import obs
    from repro.obs import ObsConfig

    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
    prev = obs.get()
    o = obs.install(ObsConfig(enabled=True))
    try:
        batches = agg_stream(n_ticks=2)
        tier = IngestTier(batches, N_SRC, 2, **tier_kw(worker="process",
                                                       backend="xla"))
        outs = list(tier)
    finally:
        obs.set_current(prev)
    assert_ordered(outs)
    starts = [e for e in o.flight.events if e["kind"] == "leaf_start"]
    assert len(starts) == 2
    assert {(e["platform"], e["backend"]) for e in starts} == {("cpu", "xla")}
    assert os.getpid() not in {e["pid"] for e in starts}


# ------------------------------------------------- runtime integration ----

def test_tier_feeds_async_runtime_with_churn():
    """The tier as a drop-in AsyncStreamRuntime source upstream of
    stage(): pipeline outputs over the live tier (with a mid-stream host
    join) equal a sync run over the tier's recorded stream."""
    from repro.core.aggregate import count_aggregate
    from repro.core.async_runtime import AsyncStreamRuntime, run_sync
    from repro.core.runtime import VSNPipeline
    from repro.core.windows import WindowSpec
    from repro.io import ReplaySource

    op = count_aggregate(WindowSpec(wa=50, ws=100, wt="multi"), k_virt=K,
                         out_cap=512, extra_slots=2, n_inputs=N_SRC)
    batches = agg_stream(n_ticks=6)
    tier = IngestTier(batches, N_SRC, 2, record=True, **tier_kw())
    tier.add_host(at_tick=3)
    pipe = VSNPipeline(op, n_max=8, n_active=4, stash_cap=256)
    rt = AsyncStreamRuntime(pipe, tier, queue_cap=3)
    rep = rt.run()
    assert rep.ticks == len(tier.emitted)

    pipe2 = VSNPipeline(op, n_max=8, n_active=4, stash_cap=256)
    _, sink = run_sync(pipe2, ReplaySource(tier.emitted, n_inputs=N_SRC))
    assert rt.sink.results() == sink.results()
    assert rt.sink.results()


# ------------------------------------------------------- backpressure -----

def test_backpressure_reaches_source_iterator():
    """A slow tier consumer must stall the source: with bounded channels
    the router can only run ahead by the channel capacities, never the
    whole stream."""
    produced = [0]

    def counting_stream():
        for b in agg_stream(n_ticks=30):
            produced[0] += 1
            yield b

    tier = IngestTier(counting_stream(), N_SRC, 2,
                      **tier_kw(chan_cap=1))
    it = iter(tier)
    for _ in range(3):
        next(it)
    time.sleep(0.3)          # router runs as far ahead as the caps allow
    ahead = produced[0]
    assert ahead < 30, "backpressure failed: source fully drained"
    assert ahead <= 3 + 12   # 3 consumed + bounded in-flight slack
    list(it)                 # drain; shutdown must leave no stuck threads
    assert produced[0] == 30


# ------------------------------------------------ overflow accounting -----

def lagging_stream(n_ticks=5, tick=16, racer=0, crawler=1, n_sources=2):
    """Source ``racer`` runs far ahead while ``crawler`` barely advances:
    the racer's tuples cannot become ready and must stash."""
    base = 0
    for _ in range(n_ticks):
        tau = np.sort(np.concatenate([
            base + 5 + 7 * np.arange(tick - 1, dtype=np.int32),
            np.asarray([base + 1], dtype=np.int32)]))
        src = np.full((tick,), racer, np.int32)
        src[int(np.argmin(tau))] = crawler
        yield T.make_batch(tau, np.zeros((tick, 1), np.float32),
                           keys=np.zeros((tick, 1), np.int32), source=src)
        base += 2


def test_leaf_overflow_counted_and_surfaced():
    """Both lagging sources on ONE leaf: the stash pressure is leaf-local
    and must be counted there and surfaced as a warning + in stats."""
    batches = list(lagging_stream())
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        tier = IngestTier(batches, 2, 1, **tier_kw(worker="inline",
                                                   leaf_cap=4, root_cap=256))
        list(tier)
    st = tier.stats()
    assert st.leaf_overflow[0] > 0
    assert any("leaf 0 stash overflow" in str(w.message) for w in rec)


def test_root_overflow_counted_and_surfaced():
    """Lagging sources on DIFFERENT leaves: each leaf's stream is locally
    ready, the stash pressure lands at the root."""
    batches = list(lagging_stream())
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        tier = IngestTier(batches, 2, 2, **tier_kw(worker="inline",
                                                   leaf_cap=64, root_cap=4))
        list(tier)
    st = tier.stats()
    assert st.root_overflow > 0
    assert sum(st.leaf_overflow.values()) == 0
    assert any("root stash overflow" in str(w.message) for w in rec)


def test_overflow_under_remove_host_flush():
    """remove_host flushes the leaving leaf's stash in one round; a root
    too small for the flood must *count* the drop, not hide it."""
    batches = list(lagging_stream(n_ticks=6, tick=24))
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        # leaf 0 owns the racing source and builds a large stash (its
        # crawling co-source gates W); removing it flushes that stash
        # through a 4-lane root in one round
        tier = IngestTier(batches, 2, 1, **tier_kw(worker="inline",
                                                   leaf_cap=256, root_cap=4))
        tier.add_host(at_tick=3)
        tier.remove_host(0, at_tick=4)
        outs = list(tier)
    assert_ordered(outs)
    st = tier.stats()
    assert st.root_overflow > 0, "flush overflow went uncounted"
    assert any("overflow" in str(w.message) for w in rec)
    # accounting is exact: everything not dropped was delivered
    assert st.tuples_out == st.tuples_in - st.total_overflow


# ------------------------------------------- merge_order tie contract -----

def tied_batch(n=37, n_sources=4, seed=7):
    """Heavy-tie tick; the default length is not a power of two, so the
    Pallas backends run the merge kernel's internal padding path."""
    rng = np.random.default_rng(seed)
    tau = np.sort(rng.integers(0, 6, n)).astype(np.int32)   # heavy ties
    src = rng.integers(0, n_sources, n).astype(np.int32)
    valid = rng.random(n) > 0.1
    return (jnp.asarray(tau), jnp.asarray(src), jnp.asarray(valid))


@pytest.mark.parametrize("backend,key_fields", [
    ("xla", ("tau", "source", "arrival")),
    ("pallas-interpret", ("tau", "arrival")),
])
def test_merge_order_tie_break_contract(backend, key_fields):
    """Each backend's documented tie-break is exactly what it sorts by."""
    tau, src, valid = tied_batch()
    assert scalegate.tie_break(backend) == key_fields
    order = np.asarray(scalegate.merge_order(tau, src, valid, 4,
                                             backend=backend))
    arrival = np.arange(tau.shape[0])
    cols = {"tau": np.where(np.asarray(valid), np.asarray(tau),
                            np.iinfo(np.int32).max),
            "source": np.asarray(src), "arrival": arrival}
    # np.lexsort keys: least-significant first
    want = np.lexsort(tuple(cols[f] for f in reversed(key_fields)))
    np.testing.assert_array_equal(order, want)


def test_merge_order_backends_agree_up_to_tie_order():
    """Cross-backend parity on tied-tau batches: same ready content, same
    per-tau lane groups — only the order within a tau group may differ."""
    tau, src, valid = tied_batch()
    o_xla = np.asarray(scalegate.merge_order(tau, src, valid, 4,
                                             backend="xla"))
    o_pal = np.asarray(scalegate.merge_order(tau, src, valid, 4,
                                             backend="pallas-interpret"))
    tau_np = np.where(np.asarray(valid), np.asarray(tau),
                      np.iinfo(np.int32).max)
    for o in (o_xla, o_pal):
        assert (np.diff(tau_np[o]) >= 0).all()      # both tau-sorted
    for t in np.unique(tau_np):
        g_xla = set(o_xla[tau_np[o_xla] == t].tolist())
        g_pal = set(o_pal[tau_np[o_pal] == t].tolist())
        assert g_xla == g_pal                        # identical tau groups


def test_push_ready_set_identical_across_backends():
    """scalegate.push emits the same ready multiset under either backend
    (the tie order inside a tau group is the only degree of freedom)."""
    tau, src, valid = tied_batch()
    b = T.make_batch(tau, np.zeros((tau.shape[0], 1), np.float32),
                     source=src, valid=valid)
    outs = {}
    for backend in ("xla", "pallas-interpret"):
        st = scalegate.init_scalegate(4, 32, 1, 1)
        _, out = scalegate.push(st, b, backend=backend)
        outs[backend] = collect_tuples([out])
    assert outs["xla"] == outs["pallas-interpret"]


def test_root_merge_tolerates_either_leaf_tie_break():
    """Leaves running different merge_order contracts feed the same root:
    output sets identical, order valid in both tiers."""
    batches = agg_stream(n_ticks=4)
    results = {}
    for backend in ("xla", "pallas-interpret"):
        tier = IngestTier(batches, N_SRC, 2,
                          **tier_kw(worker="inline", backend=backend))
        outs = list(tier)
        assert_ordered(outs)
        results[backend] = collect_tuples(outs)
    assert results["xla"] == results["pallas-interpret"]
    assert results["xla"] == collect_tuples(
        single_gate_stream(batches, N_SRC, cap=96))


# ------------------------------------------------------- partitioner ------

def test_partitioner_balanced_and_minimal_moves():
    p = SourcePartitioner(8, [0, 1])
    assert sorted(p.counts().values()) == [4, 4]
    moves = p.rebalance(add=[2])
    assert sorted(p.counts().values()) == [2, 3, 3]
    assert len(moves) == 2                     # minimal: only into leaf 2
    assert all(new == 2 for _, new in moves.values())

    moves = p.rebalance(remove=[0])
    assert 0 not in p.leaves
    assert sorted(p.counts().values()) == [4, 4]
    assert all(old == 0 for old, _ in moves.values())

    # disjoint cover at every step
    owned = [p.owned_mask(l) for l in p.leaves]
    assert np.logical_or.reduce(owned).all()
    assert (np.sum(owned, axis=0) == 1).all()


def test_partitioner_cannot_drop_last_leaf():
    p = SourcePartitioner(4, [0])
    with pytest.raises(AssertionError):
        p.rebalance(remove=[0])


# ------------------------------------------------- watermark helpers ------

def test_observe_explicit_and_clamp_frontier():
    st = wm.init_watermark(3)
    st = wm.observe_explicit(st, jnp.asarray([5, 7, 9]),
                             jnp.asarray([True, True, False]))
    np.testing.assert_array_equal(np.asarray(st.frontier), [5, 7, 0])
    # reports fold with max (never regress)
    st = wm.observe_explicit(st, jnp.asarray([3, 8, 0]),
                             jnp.asarray([True, True, True]))
    np.testing.assert_array_equal(np.asarray(st.frontier), [5, 8, 0])
    # the rebalance clamp lowers only the masked entry
    st = wm.clamp_frontier(st, jnp.asarray([False, True, False]), 6)
    np.testing.assert_array_equal(np.asarray(st.frontier), [5, 6, 0])
    assert int(st.value()) == 0
