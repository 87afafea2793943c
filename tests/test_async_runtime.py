"""Live runtime: async double-buffered ingest == synchronous host loop.

The contracts under test (ISSUE-3 acceptance):
  * exact output-set parity, async vs sync, on q1-style aggregation and
    q3-style join streams;
  * parity holds across a controller-triggered mid-stream reconfiguration,
    and the live elastic run matches the static max-width oracle;
  * the bounded in-flight queue never exceeds its cap under a slow
    consumer (backpressure blocks the producer instead of growing memory);
  * per-instance load and detection→switch latency are exposed to the
    metrics loop.
"""

import threading
import time

import numpy as np
import jax.numpy as jnp
import pytest

from conftest import collect_outputs
from repro.core.aggregate import count_aggregate
from repro.core.async_runtime import AsyncStreamRuntime, run_sync, tick_meta
from repro.core.controller import ThresholdController
from repro.core.join import band_predicate, fast_join_init, scalejoin_def
from repro.core.join import tick_fast as join_fast
from repro.core.runtime import VSNPipeline
from repro.core.vsn import merge_fast_state
from repro.core.windows import WindowSpec
from repro.data import datagen
from repro.io import (TIMEOUT, BoundedQueue, QueueClosed, RateSchedule,
                      ReplaySource, SyntheticSource, load_stream,
                      save_stream)

K = 64
WS = WindowSpec(wa=50, ws=100, wt="multi")


def agg_op():
    return count_aggregate(WS, k_virt=K, out_cap=512, extra_slots=2)


def agg_stream(n_ticks=6, seed=0):
    rng = np.random.default_rng(seed)
    return list(datagen.tweets(rng, n_ticks=n_ticks, tick=16,
                               words_per_tweet=3, vocab=500, k_virt=K,
                               rate_per_tick=30))


def agg_pipe(n_active=4, n_max=8):
    return VSNPipeline(agg_op(), n_max=n_max, n_active=n_active,
                       stash_cap=64)


# ------------------------------------------------------------- parity -----

def test_async_matches_sync_q1_style():
    batches = agg_stream()
    rt = AsyncStreamRuntime(agg_pipe(), ReplaySource(batches), queue_cap=3)
    rep = rt.run()
    _, sink = run_sync(agg_pipe(), ReplaySource(batches))
    assert rt.sink.results() == sink.results()
    assert rep.ticks == len(batches)
    assert rt.sink.results()          # non-trivial stream
    assert rep.queue_high_water <= 3


def test_async_matches_sync_q3_style_join():
    jws = WindowSpec(wa=1, ws=5000, wt="single")
    fj = band_predicate(500.0, 2)
    op = scalejoin_def(jws, K, fj, payload_width=4, ring=8)

    def join_tick(op_, st, ready, resp, explicit_w=None):
        return join_fast(jws, fj, st, ready, resp, out_cap=2048)

    def pipe():
        return VSNPipeline(op, n_max=4, n_active=4, stash_cap=16,
                           tick_fn=join_tick, merge_fn=merge_fast_state,
                           init_sigma=lambda: fast_join_init(K, 8, 4))

    rng = np.random.default_rng(3)
    batches = list(datagen.scalejoin(rng, n_ticks=5, tick=32, k_virt=1))
    rt = AsyncStreamRuntime(pipe(), ReplaySource(batches, n_inputs=2),
                            queue_cap=2)
    rt.run()
    _, sink = run_sync(pipe(), ReplaySource(batches, n_inputs=2))
    assert rt.sink.results() == sink.results()
    assert rt.sink.results()


def test_async_reconfig_parity_and_static_oracle():
    """A controller-triggered mid-stream reconfiguration: the live run's
    outputs equal (a) a sync run replaying the same reconfig trace and
    (b) the static max-width oracle."""
    batches = agg_stream(n_ticks=8)
    # 2 x 2000 t/s capacity; the 9000 t/s phase crosses the 0.90 threshold
    sched = RateSchedule(((3, 1500.0), (5, 9000.0)))
    ctl = ThresholdController(n_max=8, k_virt=K,
                              capacity_per_instance=2000.0, n_active=2)
    rt = AsyncStreamRuntime(agg_pipe(n_active=2),
                            ReplaySource(batches, schedule=sched),
                            controller=ctl, queue_cap=3)
    rep = rt.run()
    assert rep.reconfig_trace, "the rate spike never triggered the controller"
    assert rep.switches >= 1
    assert len(rep.detect_to_switch_ms) == len(rep.detect_to_switch_ticks)
    # every switch resolves >= 1 detection; coalesced reconfigs mean a
    # single switch may resolve several, but none can outlive the run by
    # more than the still-pending tail
    assert rep.switches <= len(rep.detect_to_switch_ms)
    assert len(rep.detect_to_switch_ms) <= len(rep.reconfig_trace)
    assert all(d >= 0.0 for d in rep.detect_to_switch_ms)

    outs = rt.sink.results()
    _, replay_sink = run_sync(agg_pipe(n_active=2), ReplaySource(batches),
                              reconfig_trace=rep.reconfig_trace)
    assert outs == replay_sink.results()

    _, oracle_sink = run_sync(agg_pipe(n_active=8), ReplaySource(batches))
    assert outs == oracle_sink.results()


def test_no_spurious_scaledown_before_rate_signal():
    """Without a rate hint, the controller must not act until a measured
    rate exists — at stream start the measured rate is 0.0, which would
    otherwise read as idle and collapse capacity on the first tick."""
    batches = agg_stream(n_ticks=4)
    ctl = ThresholdController(n_max=8, k_virt=K,
                              capacity_per_instance=2000.0, n_active=4)
    rt = AsyncStreamRuntime(agg_pipe(n_active=4), ReplaySource(batches),
                            controller=ctl, queue_cap=2)
    rep = rt.run()
    assert all(t >= 2 for t, _ in rep.reconfig_trace)


def test_sync_controller_matches_static_oracle():
    """The closed loop through run_sync (controller consulted per tick)
    also stays exact — elasticity never changes the output set."""
    batches = agg_stream(n_ticks=8)
    sched = RateSchedule(((2, 1500.0), (3, 9000.0), (3, 400.0)))
    ctl = ThresholdController(n_max=8, k_virt=K,
                              capacity_per_instance=2000.0, n_active=2)
    rep, sink = run_sync(agg_pipe(n_active=2),
                         ReplaySource(batches, schedule=sched),
                         controller=ctl)
    assert rep.reconfig_trace
    _, oracle_sink = run_sync(agg_pipe(n_active=8), ReplaySource(batches))
    assert sink.results() == oracle_sink.results()


# ------------------------------------------------------ metrics/load -----

def test_per_instance_load_exposed():
    pipe = agg_pipe(n_active=4)
    b = agg_stream(n_ticks=1)[0]
    _, _, _, inst_load = pipe.step_staged(pipe.stage(b))
    load = np.asarray(inst_load)
    assert load.shape == (8,)
    # 16 tuples x 3 keys routed to the 4 active instances
    assert load.sum() == 48
    assert (load[4:] == 0).all()

    # the host-side fallback (mesh path) agrees with the device count
    meta = tick_meta(b, 0, 1, K, np.zeros((1,), np.int64))
    fmu = np.asarray(pipe.epoch.fmu)
    host_load = np.bincount(fmu, weights=meta.key_hist, minlength=8)
    np.testing.assert_array_equal(host_load, load)


def test_snapshot_pairs_load_with_observed_active():
    """A load sample is judged under the active count it was measured
    with, not whatever the shadow says later (no phantom skew)."""
    from repro.io import MetricsBus
    m = MetricsBus()
    m.start()
    m.record_tick(0, 10, 0.01, np.array([5.0, 5.0, 0.0, 0.0]), 0,
                  n_active=2)
    snap = m.snapshot(rate_hint=100.0)
    assert snap.n_active_observed == 2
    assert snap.load_skew(snap.n_active_observed) == 1.0


def test_detection_to_switch_accounting():
    batches = agg_stream(n_ticks=6)
    sched = RateSchedule(((2, 1500.0), (4, 9000.0)))
    ctl = ThresholdController(n_max=8, k_virt=K,
                              capacity_per_instance=2000.0, n_active=2)
    rt = AsyncStreamRuntime(agg_pipe(n_active=2),
                            ReplaySource(batches, schedule=sched),
                            controller=ctl, queue_cap=2)
    rep = rt.run()
    assert rep.switches >= 1
    # switch can never be observed before its detection
    assert all(t >= 0 for t in rep.detect_to_switch_ticks)


# ------------------------------------------------------- backpressure -----

def test_bounded_queue_backpressure_slow_consumer():
    """Depth never exceeds the cap while a fast producer feeds a slow
    consumer; the producer blocks instead."""
    q = BoundedQueue(3)
    seen, depths = [], []

    def produce():
        for i in range(20):
            q.put(i)
        q.close()

    t = threading.Thread(target=produce)
    t.start()
    try:
        while True:
            depths.append(q.depth)
            item = q.get(timeout=5)
            if item is TIMEOUT:
                pytest.fail("starved: producer made no progress in 5s")
            seen.append(item)
            time.sleep(0.002)       # slow consumer
    except QueueClosed:
        pass
    t.join()
    assert seen == list(range(20))  # FIFO, nothing lost
    assert q.high_water <= 3        # never exceeded the cap
    assert max(depths) <= 3
    assert q.blocked_puts > 0       # the producer actually blocked


def test_bounded_queue_put_after_close_raises():
    q = BoundedQueue(2)
    q.close()
    with pytest.raises(QueueClosed):
        q.put(1)
    with pytest.raises(QueueClosed):
        q.get()


def test_bounded_queue_get_disambiguates_timeout_from_close():
    """Regression (ISSUE-4 satellite): ``get`` used to look the same on a
    timed-out wait and on end-of-stream.  Now: TIMEOUT sentinel while the
    queue is open, items enqueued before close still drain, and only the
    drained+closed queue raises QueueClosed."""
    q = BoundedQueue(2)
    assert q.get(timeout=0.01) is TIMEOUT      # open + empty: not an end
    q.put("a")
    q.put("b")
    q.close()
    assert q.get(timeout=0.01) == "a"          # close never loses items
    assert q.get() == "b"
    with pytest.raises(QueueClosed):           # ...and only then ends
        q.get(timeout=0.01)


def test_runtime_queue_respects_cap():
    batches = agg_stream(n_ticks=6)
    rt = AsyncStreamRuntime(agg_pipe(), ReplaySource(batches), queue_cap=2)
    rt.run()
    assert rt.queue.high_water <= 2


# ------------------------------------------------------------ io misc -----

def test_save_load_stream_roundtrip(tmp_path):
    batches = agg_stream(n_ticks=3)
    path = str(tmp_path / "stream.npz")
    save_stream(path, batches, n_inputs=1)
    src = load_stream(path)
    assert src.n_inputs == 1 and len(src) == 3
    for a, b in zip(batches, src):
        np.testing.assert_array_equal(np.asarray(a.tau), np.asarray(b.tau))
        np.testing.assert_array_equal(np.asarray(a.keys), np.asarray(b.keys))
        np.testing.assert_array_equal(np.asarray(a.payload),
                                      np.asarray(b.payload))


def test_sink_keeps_large_payload_values_exact():
    """Key ids and counts above ~1678 survive the sink's 4-decimal
    rounding unchanged (float32 rounding turned 26869 into 26868.998)."""
    from repro.core.operator import Outputs
    from repro.io.sinks import flatten_outputs

    pay = np.array([[26869., 3.], [65535., 49152.], [0.25, 1.]], np.float32)
    outs = Outputs(tau=np.array([1000, 1000, 2000], np.int32), payload=pay,
                   valid=np.array([True, True, True]),
                   count=np.int32(3), overflow=np.int32(0))
    assert flatten_outputs(outs) == [(1000, (26869.0, 3.0)),
                                     (1000, (65535.0, 49152.0)),
                                     (2000, (0.25, 1.0))]


def test_rate_schedule():
    s = RateSchedule(((2, 100.0), (3, 900.0)))
    assert [s.rate_at(i) for i in range(7)] == [100., 100., 900., 900.,
                                                900., 900., 900.]
    assert s.total_ticks == 5


def test_paced_source_spacing():
    batches = agg_stream(n_ticks=3)
    src = SyntheticSource(batches, schedule=RateSchedule(((3, 3200.0),)),
                          pace=True, tick_size=16)
    t0 = time.perf_counter()
    got = list(src)
    dt = time.perf_counter() - t0
    assert len(got) == 3
    assert dt >= 2 * 16 / 3200.0    # at least two inter-tick gaps
