"""Core O+ semantics: window math, watermarks, ScaleGate, the Appendix-E
trace, Observation 1 and Lemma 2."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from conftest import collect_outputs, make_stream_batch
from repro.core import scalegate, tuples as T, watermark as wm
from repro.core.aggregate import count_aggregate, longest_aggregate
from repro.core.operator import tick as gen_tick
from repro.core.windows import WindowSpec


# ---------------------------------------------------------------- windows --
@given(st.integers(1, 20), st.integers(1, 60), st.integers(-1000, 1000))
@settings(max_examples=200, deadline=None)
def test_window_index_invariants(wa, ws_extra, tau):
    ws = wa + ws_extra  # WS > WA (sliding, §3)
    spec = WindowSpec(wa=wa, ws=ws)
    l_min, l_max = spec.window_indices(jnp.int32(tau))
    l_min, l_max = int(l_min), int(l_max)
    # tuple falls in every window of the range and no window outside it
    for l in range(l_min - 1, l_max + 2):
        inside = l * wa <= tau < l * wa + ws
        assert inside == (l_min <= l <= l_max)
    # at most ceil(WS/WA) windows (paper §2.1)
    assert 1 <= l_max - l_min + 1 <= -(-ws // wa)


def test_expiry_boundary():
    spec = WindowSpec(wa=10, ws=20)
    # window [0, 20) is expired exactly once W >= 20 (Definition 2)
    assert not bool(spec.expired(0, 19))
    assert bool(spec.expired(0, 20))


# -------------------------------------------------------------- watermark --
def test_watermark_min_over_sources():
    st_ = wm.init_watermark(3)
    st_ = wm.observe(st_, jnp.asarray([0, 1, 2]), jnp.asarray([5, 9, 3]),
                     jnp.ones(3, bool))
    assert int(st_.value()) == 3  # Definition 3: min over per-source max


def test_watermark_remove_source_unblocks():
    st_ = wm.init_watermark(2)
    st_ = wm.observe(st_, jnp.asarray([0]), jnp.asarray([50]),
                     jnp.ones(1, bool))
    assert int(st_.value()) == 0          # source 1 silent
    st_ = wm.remove_sources(st_, jnp.asarray([False, True]))
    assert int(st_.value()) == 50         # flush semantics (§6)


def test_watermark_add_source_lemma3():
    st_ = wm.init_watermark(2, active=jnp.asarray([True, False]))
    st_ = wm.observe(st_, jnp.asarray([0]), jnp.asarray([40]),
                     jnp.ones(1, bool))
    st_ = wm.add_sources(st_, jnp.asarray([False, True]), gamma=40)
    # the provisioned source starts at gamma, not 0 (Lemma 3)
    assert int(st_.value()) == 40


# -------------------------------------------------------------- scalegate --
@given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 100)),
                min_size=1, max_size=40))
@settings(max_examples=100, deadline=None)
def test_scalegate_invariants(items):
    """Ready tuples are sorted, exactly-once, and never exceed W."""
    n_sources = 4
    # per-source sorted streams
    per_src = {i: sorted(t for s, t in items if s == i)
               for i in range(n_sources)}
    taus, srcs = [], []
    idxs = {i: 0 for i in range(n_sources)}
    for s, _ in items:
        taus.append(per_src[s][idxs[s]])
        srcs.append(s)
        idxs[s] += 1
    state = scalegate.init_scalegate(n_sources, capacity=64, kmax=1,
                                     payload_width=1)
    batch = make_stream_batch(taus, source=np.asarray(srcs, np.int32))
    state, out = scalegate.push(state, batch)
    w = int(state.wmark.value())
    got = [(int(t), int(s)) for t, s, ok in
           zip(np.asarray(out.tau), np.asarray(out.source),
               np.asarray(out.valid)) if ok]
    # sorted
    assert all(got[i][0] <= got[i + 1][0] for i in range(len(got) - 1))
    # never beyond the watermark (Definition 3)
    assert all(t <= w for t, _ in got)
    # exactly the input tuples with tau <= w (exactly-once, Definition 6)
    expect = sorted((t, s) for t, s in zip(taus, srcs) if t <= w)
    assert sorted(got) == expect
    assert int(state.overflow) == 0


def test_scalegate_carryover():
    state = scalegate.init_scalegate(2, capacity=8, kmax=1, payload_width=1)
    b1 = make_stream_batch([5, 9], source=np.asarray([0, 0], np.int32))
    state, out1 = scalegate.push(state, b1)      # source 1 silent: W=0
    assert collect_outputs(out1) == []
    b2 = make_stream_batch([7], source=np.asarray([1], np.int32))
    state, out2 = scalegate.push(state, b2)      # W=min(9,7)=7 -> 5,7 ready
    assert [t for t, _ in collect_outputs(out2)] == [5, 7]


# ------------------------------------------------- Appendix E trace (A+) ---
def test_appendix_e_longest_tweet_trace():
    """The paper's Execution Trace 1: A+ (WA=30min, WS=1h, WT=multi) on the
    running example; we use minutes as delta ticks."""
    ws = WindowSpec(wa=30, ws=60, wt="multi")
    # virtual keys: pink=0, red=1
    op = longest_aggregate(ws, k_virt=2, out_cap=16).resolved()
    st_ = op.init_state()
    resp = jnp.ones((2,), bool)
    # 09:30->570, 09:50->590, 09:58->598; payload[0] = length
    b1 = make_stream_batch([590], keys=[[0, -1]],
                           payload=np.asarray([[11.]], np.float32), kmax=2)
    st_, _ = gen_tick(op, st_, b1, resp)
    b2 = make_stream_batch([598], keys=[[1, 0]],
                           payload=np.asarray([[13.]], np.float32), kmax=2)
    st_, _ = gen_tick(op, st_, b2, resp)
    acc = np.asarray(st_.zeta["acc"])[:, :, 0]
    occ = np.asarray(st_.occupied)
    # windows 09:00 (l=18) and 09:30 (l=19): pink=13, red=13 in both
    for l in (18, 19):
        s = l % op.slots
        assert occ[0, s] and occ[1, s]
        assert acc[0, s] == 13.0 and acc[1, s] == 13.0
    # advance watermark past 10:00 (=600): both keys output at 600 (Fig. 15)
    b3 = make_stream_batch([640], keys=[[-1, -1]], kmax=2)
    st_, outs = gen_tick(op, st_, b3, resp)
    got = collect_outputs(outs)
    assert (600, (0.0, 13.0)) in got and (600, (1.0, 13.0)) in got


# -------------------------------------------- Observation 1 and Lemma 2 ----
def test_output_timestamps_after_inputs_and_sorted():
    ws = WindowSpec(wa=5, ws=10, wt="multi")
    op = count_aggregate(ws, k_virt=4, out_cap=128).resolved()
    st_ = op.init_state()
    rng = np.random.default_rng(0)
    taus = np.sort(rng.integers(0, 200, 64))
    keys = rng.integers(0, 4, 64)
    all_out = []
    for i in range(0, 64, 16):
        b = make_stream_batch(taus[i:i + 16], keys=keys[i:i + 16])
        st_, outs = gen_tick(op, st_, b, jnp.ones((4,), bool))
        all_out += collect_outputs(outs)
    # Observation 1: every output tau exceeds every contributing input tau
    # (weakly: output tau = right boundary > window tuples)
    # Lemma 2: the f_O output stream is timestamp-sorted
    ts = [t for t, _ in all_out]
    assert ts == sorted(ts)
    assert min(ts) > int(taus.min())


# ------------------------------------------------- output buffer append ---
@pytest.mark.parametrize("n,cap,count", [(300, 64, 0), (300, 64, 50),
                                         (1000, 512, 7), (129, 40, 0),
                                         (5, 8, 8), (20000, 64, 3)])
def test_emit_appends_valid_rows_in_order(n, cap, count):
    """``_emit`` appends exactly the valid rows, in row order, after the
    lanes already filled, drops and counts what does not fit, and leaves
    the filled lanes alone: with groups of its two-level count of one row,
    of a few, and of the most (``EMIT_ROW``), and with one time for all
    rows or one per row."""
    import dataclasses
    from repro.core.operator import _emit, _empty_outputs

    rng = np.random.default_rng(n + cap + count)
    valid = rng.random(n) < 0.4
    pay = rng.normal(size=(n, 2)).astype(np.float32)
    taus = rng.integers(0, 100, n).astype(np.int32)
    lane = np.arange(cap)
    outs = dataclasses.replace(
        _empty_outputs(cap, 2), count=jnp.int32(count),
        valid=jnp.asarray(lane < count), tau=jnp.full((cap,), -5, jnp.int32),
        payload=jnp.full((cap, 2), -7.0, jnp.float32))
    rows = np.nonzero(valid)[0][:cap - count]
    for tau in (taus, 42):
        got = _emit(outs, jnp.asarray(tau), jnp.asarray(valid),
                    lambda idx: jnp.asarray(pay)[idx])
        want_tau = np.full(cap, -5)
        want_tau[count:count + rows.size] = (taus[rows] if np.ndim(tau)
                                             else tau)
        want_pay = np.full((cap, 2), -7.0, np.float32)
        want_pay[count:count + rows.size] = pay[rows]
        np.testing.assert_array_equal(np.asarray(got.tau), want_tau)
        np.testing.assert_array_equal(np.asarray(got.payload), want_pay)
        np.testing.assert_array_equal(np.asarray(got.valid),
                                      lane < count + rows.size)
        assert int(got.count) == min(count + valid.sum(), cap)
        assert int(got.overflow) == max(count + valid.sum() - cap, 0)
