"""Property-based cross-backend conformance fuzz.

Every ``<name>_op`` entry point must present *identical stream semantics*
on every backend — the Röger/Mayer elasticity-survey point: reconfiguration
parity between backends is meaningless unless the backends agree tuple-for-
tuple in the first place.  The suite drives randomized shapes (including
the padding edges the 2-D tiled rewrites introduced: non-multiple-of-128
hit blocks, non-multiple-of-8 join blocks, non-power-of-two merge ticks),
duplicate keys, all-equal and all-INF tau, single-source and all-invalid
lanes through ``xla`` ⇄ ``pallas-interpret`` and asserts *exact* parity on
integer outputs (order, readiness, watermark, counts, comparisons) and
tight-atol parity on float accumulations.

Shapes are drawn from small buckets (each distinct shape is a fresh jit
trace); runs are derandomized for a deterministic CI signal.  Works with
real hypothesis or the deterministic ``tests/_hypothesis_fallback`` shim.
Heavy sweeps live at the bottom behind ``@pytest.mark.slow``.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.watermark import INF_TIME
from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.linear_scan.ops import linear_scan_op
from repro.kernels.scalegate_merge.ops import scalegate_merge_op
from repro.kernels.segment_aggregate.ops import segment_aggregate_op
from repro.kernels.window_join.ops import window_join_op

BACKENDS = ("xla", "pallas-interpret")
INF = int(INF_TIME)


# ------------------------------------------------------------------ merge --

def _merge_batch(n, n_sources, seed, mode):
    rng = np.random.default_rng(seed)
    tau = rng.integers(0, 50, n).astype(np.int32)     # heavy tau duplicates
    src = rng.integers(0, n_sources, n).astype(np.int32)
    valid = rng.random(n) < 0.8
    if mode == "ties":
        tau = rng.integers(0, 3, n).astype(np.int32)
    elif mode == "all_equal":
        tau[:] = 7
        valid[:] = True
    elif mode == "all_inf":
        tau[:] = INF
        valid[:] = True
    elif mode == "single_source":
        src[:] = 0                  # other frontiers stay empty -> W = -1
    elif mode == "all_invalid":
        valid[:] = False
    return tau, src, valid


@settings(max_examples=10, deadline=None, derandomize=True)
@given(st.sampled_from([5, 32, 48, 128]),      # incl. non-power-of-two
       st.sampled_from([1, 2, 4]),
       st.integers(0, 10 ** 6),
       st.sampled_from(["random", "ties", "all_equal", "all_inf",
                        "single_source", "all_invalid"]))
def test_scalegate_merge_conformance(n, n_sources, seed, mode):
    tau, src, valid = _merge_batch(n, n_sources, seed, mode)
    got = {b: scalegate_merge_op(tau, src, valid, n_sources=n_sources,
                                 backend=b) for b in BACKENDS}
    o_x, r_x, w_x = (np.asarray(a) for a in got["xla"])
    o_p, r_p, w_p = (np.asarray(a) for a in got["pallas-interpret"])
    # (tau, lane) keys are unique: the total order itself is exact
    np.testing.assert_array_equal(o_x, o_p)
    np.testing.assert_array_equal(r_x, r_p)
    assert int(w_x[0]) == int(w_p[0])
    # independent oracle: the documented (tau, arrival) lexicographic order
    key = np.where(valid, tau.astype(np.int64), INF)
    np.testing.assert_array_equal(o_x, np.lexsort((np.arange(n), key)))
    # readiness = valid and tau <= W, in sorted positions
    np.testing.assert_array_equal(
        r_x, (valid[o_x] & (tau[o_x].astype(np.int64) <= int(w_x[0]))))


# -------------------------------------------------------------- aggregate --

@settings(max_examples=10, deadline=None, derandomize=True)
@given(st.sampled_from([1, 16, 33, 128]),      # incl. lane-padding edges
       st.sampled_from([8, 32]),
       st.sampled_from([1, 4]),
       st.sampled_from([1, 3]),
       st.integers(0, 10 ** 6))
def test_segment_aggregate_conformance(n, k, s, w, seed):
    rng = np.random.default_rng(seed)
    # keys out of range on both sides + duplicates; integer-valued floats
    # keep every partial sum exactly representable -> exact parity
    keys = rng.integers(-2, k + 3, n).astype(np.int32)
    slots = rng.integers(0, s, n).astype(np.int32)
    vals = rng.integers(0, 3, (n, w)).astype(np.float32)
    acc = rng.integers(0, 5, (k, s, w)).astype(np.float32)
    outs = [np.asarray(segment_aggregate_op(keys, slots, vals, acc,
                                            tile_k=tile, backend=b))
            for b in BACKENDS for tile in (k, 8)]
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)
    # conservation: in-range hits land exactly once
    in_range = (keys >= 0) & (keys < k)
    assert outs[0].sum() == acc.sum() + vals[in_range].sum()


# ------------------------------------------------------------------- join --

def _join_case(b, k, r, seed, mode):
    rng = np.random.default_rng(seed)
    new_tau = np.sort(rng.integers(50, 120, b)).astype(np.int32)
    new_src = rng.integers(0, 2, b).astype(np.int32)
    # integer payloads: the |d| <= band boundary is exact on every backend
    new_pay = rng.integers(0, 12, (b, 2)).astype(np.float32)
    st_tau = rng.integers(0, 110, (k, r)).astype(np.int32)
    st_tau[rng.random((k, r)) < 0.3] = -1
    st_src = rng.integers(0, 2, (k, r)).astype(np.int32)
    st_pay = rng.integers(0, 12, (k, r, 2)).astype(np.float32)
    if mode == "all_invalid":                  # static-batch padding lanes
        new_tau[:] = INF
    elif mode == "empty_store":
        st_tau[:] = -1
    elif mode == "single_stream":
        new_src[:] = 0
        st_src[:] = 0                          # no opposite pairs at all
    return new_tau, new_src, new_pay, st_tau, st_src, st_pay


@settings(max_examples=10, deadline=None, derandomize=True)
@given(st.sampled_from([1, 7, 8, 30]),         # incl. non-multiple-of-8
       st.sampled_from([16, 64]),
       st.sampled_from([2, 5]),
       st.integers(0, 10 ** 6),
       st.sampled_from(["random", "all_invalid", "empty_store",
                        "single_stream"]))
def test_window_join_conformance(b, k, r, seed, mode):
    args = _join_case(b, k, r, seed, mode)
    got = {bk: window_join_op(*args, ws=40, band=4.0, tile_k=16, backend=bk)
           for bk in BACKENDS}
    c_x, n_x = got["xla"]
    c_p, n_p = got["pallas-interpret"]
    np.testing.assert_array_equal(np.asarray(c_x), np.asarray(c_p))
    assert int(n_x) == int(n_p)
    if mode in ("all_invalid", "empty_store", "single_stream"):
        assert int(n_x) == 0 and not np.asarray(c_x).any()


# -------------------------------------------------------------- attention --

@settings(max_examples=6, deadline=None, derandomize=True)
@given(st.sampled_from([(16, 16, 1), (1, 64, 1), (16, 32, 2)]),  # decode+GQA
       st.booleans(),
       st.sampled_from([None, 8]),
       st.integers(0, 10 ** 6))
def test_flash_attention_conformance(shape, causal, window, seed):
    sq, skv, n_rep = shape
    rng = np.random.default_rng(seed)
    bh_kv, d = 2, 16
    q = rng.normal(0, 1, (bh_kv * n_rep, sq, d)).astype(np.float32)
    k = rng.normal(0, 1, (bh_kv, skv, d)).astype(np.float32)
    v = rng.normal(0, 1, (bh_kv, skv, d)).astype(np.float32)
    outs = [np.asarray(flash_attention_op(
        q, k, v, causal=causal, window=window, n_rep=n_rep,
        blk_q=min(16, sq), blk_k=16, backend=b)) for b in BACKENDS]
    np.testing.assert_allclose(outs[0], outs[1], atol=2e-5)
    assert np.isfinite(outs[0]).all()


# ------------------------------------------------------------------- scan --

@settings(max_examples=6, deadline=None, derandomize=True)
@given(st.sampled_from([(1, 16, 4, 4), (2, 64, 8, 16)]),
       st.booleans(),
       st.integers(0, 10 ** 6))
def test_linear_scan_conformance(shape, bonus, seed):
    bh, t, dk, dv = shape
    rng = np.random.default_rng(seed)
    r = rng.normal(0, 1, (bh, t, dk)).astype(np.float32)
    k = rng.normal(0, 1, (bh, t, dk)).astype(np.float32)
    v = rng.normal(0, 1, (bh, t, dv)).astype(np.float32)
    w = rng.uniform(0.5, 0.99, (bh, t, dk)).astype(np.float32)
    u = rng.normal(0, 1, (bh, dk)).astype(np.float32) if bonus else None
    outs = [np.asarray(linear_scan_op(r, k, v, w, u, chunk=16, backend=b))
            for b in BACKENDS]
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-4)


# ---------------------------------------------------- TIE_BREAK contract --

@settings(max_examples=8, deadline=None, derandomize=True)
@given(st.sampled_from([13, 16, 40]),          # incl. non-power-of-two
       st.integers(0, 10 ** 6))
def test_merge_order_tie_break_contract(n, seed):
    """Equal-tau batches: each backend's ``merge_order`` emission matches
    its *documented* ``TIE_BREAK`` sort key exactly, and the two orders
    always agree on the ready set (same lanes, possibly reordered ties).
    Every length goes through the Pallas kernel under ``pallas-*``."""
    import jax.numpy as jnp

    from repro.core import scalegate

    rng = np.random.default_rng(seed)
    n_sources = 3
    tau = rng.integers(0, 2, n).astype(np.int32)       # massive ties
    src = rng.integers(0, n_sources, n).astype(np.int32)
    valid = rng.random(n) < 0.9
    fields = {"tau": np.where(valid, tau.astype(np.int64), INF),
              "source": src.astype(np.int64),
              "arrival": np.arange(n)}
    perms = {}
    for backend in BACKENDS:
        order = np.asarray(scalegate.merge_order(
            jnp.asarray(tau), jnp.asarray(src), jnp.asarray(valid),
            n_sources, backend=backend))
        key = scalegate.tie_break(backend)
        # np.lexsort keys are least-significant first
        expect = np.lexsort(tuple(fields[f] for f in reversed(key)))
        np.testing.assert_array_equal(order, expect, err_msg=backend)
        perms[backend] = order
    # both contracts deliver the same lanes in every tau class
    for t in np.unique(tau):
        sel = valid & (tau == t)
        for p in perms.values():
            pos = np.isin(p, np.nonzero(sel)[0])
            assert set(p[pos]) == set(np.nonzero(sel)[0])


# ------------------------------------------------------------ heavy @slow --

@pytest.mark.slow
@pytest.mark.parametrize("n,n_sources", [(512, 3), (1024, 6)])
def test_scalegate_merge_conformance_heavy(n, n_sources):
    tau, src, valid = _merge_batch(n, n_sources, seed=n, mode="ties")
    o_x, r_x, w_x = scalegate_merge_op(tau, src, valid,
                                       n_sources=n_sources, backend="xla")
    o_p, r_p, w_p = scalegate_merge_op(tau, src, valid,
                                       n_sources=n_sources,
                                       backend="pallas-interpret")
    np.testing.assert_array_equal(np.asarray(o_x), np.asarray(o_p))
    np.testing.assert_array_equal(np.asarray(r_x), np.asarray(r_p))
    assert int(w_x[0]) == int(w_p[0])


@pytest.mark.slow
@pytest.mark.parametrize("b,k,r", [(256, 512, 16), (63, 128, 32)])
def test_window_join_conformance_heavy(b, k, r):
    args = _join_case(b, k, r, seed=b + k, mode="random")
    c_x, n_x = window_join_op(*args, ws=40, band=4.0, backend="xla")
    c_p, n_p = window_join_op(*args, ws=40, band=4.0,
                              backend="pallas-interpret")
    np.testing.assert_array_equal(np.asarray(c_x), np.asarray(c_p))
    assert int(n_x) == int(n_p)


@pytest.mark.slow
@pytest.mark.parametrize("n,k", [(1000, 256), (4096, 128)])
def test_segment_aggregate_conformance_heavy(n, k):
    rng = np.random.default_rng(n)
    keys = rng.integers(-2, k + 3, n).astype(np.int32)
    slots = rng.integers(0, 4, n).astype(np.int32)
    vals = rng.integers(0, 3, (n, 2)).astype(np.float32)
    acc = np.zeros((k, 4, 2), np.float32)
    a = segment_aggregate_op(keys, slots, vals, acc, tile_k=128,
                             backend="pallas-interpret")
    b = segment_aggregate_op(keys, slots, vals, acc, backend="xla")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
