"""Small-shape parity: ``xla`` vs ``pallas-interpret`` for all five kernels.

These run by default on every host: the dispatched backends must never
silently diverge from the ref oracle.  The *heavy* interpret-mode shape
sweeps live in test_kernels.py behind ``@pytest.mark.slow``.
"""

import functools

import numpy as np
import pytest

from repro.kernels import dispatch
from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.linear_scan.ops import linear_scan_op
from repro.kernels.scalegate_merge.ops import scalegate_merge_op
from repro.kernels.segment_aggregate.ops import segment_aggregate_op
from repro.kernels.window_join.ops import window_join_op

KERNELS = ("scalegate_merge", "segment_aggregate", "window_join",
           "flash_attention", "linear_scan")


def test_all_kernels_registered_on_all_backends():
    reg = dispatch.registered()
    for name in KERNELS:
        assert reg.get(name) == ("pallas", "pallas-interpret", "xla"), name


def test_cpu_default_backend_is_xla():
    import jax
    if jax.devices()[0].platform != "tpu":
        assert dispatch.default_backend() == "xla"


def test_backend_resolution_order(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas-interpret")
    assert dispatch.default_backend() == "pallas-interpret"
    dispatch.set_default_backend("xla")          # explicit beats env
    try:
        assert dispatch.default_backend() == "xla"
    finally:
        dispatch.set_default_backend(None)
    with pytest.raises(dispatch.UnknownBackendError):
        dispatch.resolve("cuda")


def test_scalegate_merge_parity():
    rng = np.random.default_rng(0)
    n, srcs = 32, 3
    tau = rng.integers(0, 500, n).astype(np.int32)
    src = rng.integers(0, srcs, n).astype(np.int32)
    valid = rng.random(n) < 0.85
    o1, r1, w1 = scalegate_merge_op(tau, src, valid, n_sources=srcs,
                                    backend="pallas-interpret")
    o2, r2, w2 = scalegate_merge_op(tau, src, valid, n_sources=srcs,
                                    backend="xla")
    # keys are unique (tau, lane): the total order itself must match
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    assert int(w1[0]) == int(w2[0])


def test_scalegate_merge_parity_full_tau_range():
    """The lexicographic (tau, lane) network has no packed-key overflow:
    epoch-style timestamps near int32 max still sort correctly."""
    rng = np.random.default_rng(7)
    n, srcs = 64, 2
    tau = rng.integers(1_500_000_000, 2_000_000_000, n).astype(np.int32)
    src = rng.integers(0, srcs, n).astype(np.int32)
    valid = rng.random(n) < 0.9
    o1, r1, w1 = scalegate_merge_op(tau, src, valid, n_sources=srcs,
                                    backend="pallas-interpret")
    o2, r2, w2 = scalegate_merge_op(tau, src, valid, n_sources=srcs,
                                    backend="xla")
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    assert int(w1[0]) == int(w2[0])
    srt = tau[np.asarray(o1)][valid[np.asarray(o1)]]
    assert (np.diff(srt) >= 0).all()


def test_segment_aggregate_parity():
    rng = np.random.default_rng(1)
    n, k, s, w = 16, 32, 2, 2
    keys = rng.integers(-1, k, n).astype(np.int32)
    slots = rng.integers(0, s, n).astype(np.int32)
    vals = rng.uniform(0, 1, (n, w)).astype(np.float32)
    acc = rng.uniform(0, 1, (k, s, w)).astype(np.float32)
    a = segment_aggregate_op(keys, slots, vals, acc, tile_k=32,
                             backend="pallas-interpret")
    b = segment_aggregate_op(keys, slots, vals, acc, backend="xla")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_segment_aggregate_out_of_range_keys_dropped_on_both_backends():
    """keys >= K are dead lanes on *both* backends (the ref used to clip
    them into row K-1 while the kernel dropped them)."""
    import jax.numpy as jnp
    k, s, w = 8, 2, 1
    keys = np.asarray([0, 7, 8, 100, -1], np.int32)     # 2 in range
    slots = np.zeros(5, np.int32)
    vals = np.ones((5, w), np.float32)
    acc = jnp.zeros((k, s, w), jnp.float32)
    a = segment_aggregate_op(keys, slots, vals, acc, tile_k=8,
                             backend="pallas-interpret")
    b = segment_aggregate_op(keys, slots, vals, acc, backend="xla")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    assert float(np.asarray(b).sum()) == 2.0            # only keys 0 and 7


# tiles of 64 keys x 3 slots; hit blocks of 512 lanes
K_SEG, S_SEG, TILE_SEG = 512, 3, 64
SEG_CASES = ["skewed", "all_dead", "one_tile", "every_tile",
             "tile_boundary_in_block", "ragged_n", "slot_out_of_range"]


def _segment_case(case: str, w: int):
    """(keys, slots, vals, acc) of one grouped-schedule parity case."""
    rng = np.random.default_rng(10 * SEG_CASES.index(case) + w)
    n = 1000 if case == "ragged_n" else 1536
    keys = rng.integers(0, K_SEG, n)
    slots = rng.integers(0, S_SEG, n)
    if case == "skewed":            # a few hot cells take most hits
        keys = (rng.zipf(1.6, n) - 1) % 8 * 67
        slots = rng.integers(0, 2, n)
    elif case == "all_dead":        # out of range on either side
        keys = rng.choice([-1, -7, K_SEG, K_SEG + 9], n)
    elif case == "one_tile":        # three hit blocks, all in tile 2
        keys = rng.integers(2 * TILE_SEG, 3 * TILE_SEG, n)
    elif case == "tile_boundary_in_block":
        # 300 hits of tile 0, then tile 1: its hits share block 0
        keys = np.where(np.arange(n) < 300, rng.integers(0, TILE_SEG, n),
                        rng.integers(TILE_SEG, 2 * TILE_SEG, n))
    elif case == "slot_out_of_range":
        slots = rng.integers(-2, S_SEG + 2, n)
    vals = rng.integers(0, 4, (n, w))
    acc = rng.integers(0, 5, (K_SEG, S_SEG, w))
    return (keys.astype(np.int32), slots.astype(np.int32),
            vals.astype(np.float32), acc.astype(np.float32))


@pytest.mark.parametrize("w", [1, 3])
@pytest.mark.parametrize("case", SEG_CASES)
def test_segment_aggregate_grouped_parity(case, w):
    """The grouped schedule reduces integer values bit-identically to the
    xla scatter-add, and a hit whose key or slot is out of range is
    dropped on both backends."""
    keys, slots, vals, acc = _segment_case(case, w)
    a = np.asarray(segment_aggregate_op(keys, slots, vals, acc,
                                        tile_k=TILE_SEG,
                                        backend="pallas-interpret"))
    b = np.asarray(segment_aggregate_op(keys, slots, vals, acc,
                                        backend="xla"))
    np.testing.assert_array_equal(a, b)
    live = ((keys >= 0) & (keys < K_SEG) & (slots >= 0)
            & (slots < S_SEG))
    assert a.sum() == acc.sum() + vals[live].sum()


def _pallas_grids(jaxpr):
    """Grids of every ``pallas_call`` in a jaxpr, its loop bodies too;
    each grid is as long as the call's schedule (its first operand)."""
    from repro.kernels.lowering import _sub_jaxprs
    out = []
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            grid = e.params["grid_mapping"].grid
            assert grid == e.invars[0].aval.shape, (grid, e.invars[0].aval)
            out.append(grid)
    for sub in _sub_jaxprs(jaxpr):
        out += _pallas_grids(sub)
    return out


@pytest.mark.parametrize("n,k,tile_k", [(16, 32, 32), (1000, 512, 64),
                                        (1536, 512, 64), (460872, 65536,
                                                          128),
                                        (300, 65536, 128)])
def test_segment_aggregate_grid_steps(n, k, tile_k):
    """The visit grid grows with tiles plus blocks, not their product, and
    is the grid the kernel builds (one call a chunk of visits)."""
    import jax
    from repro.kernels.segment_aggregate.segment_aggregate import (
        CHUNK, grid_steps, segment_aggregate)

    visits, dense = grid_steps(n, k, tile_k)
    n_tiles, n_blocks = k // tile_k, -(-n // 512)
    assert dense == n_tiles * n_blocks
    assert visits <= n_tiles + n_blocks
    traced = jax.make_jaxpr(functools.partial(segment_aggregate,
                                              tile_k=tile_k))(
        np.zeros(n, np.int32), np.zeros(n, np.int32),
        np.zeros((n, 1), np.float32), np.zeros((k, 2, 1), np.float32))
    n_pad = -(-n // 128) * 128 if n < 512 else n_blocks * 512
    padded, _ = grid_steps(n_pad, k, tile_k)
    assert visits <= padded <= visits + n_pad - n
    assert _pallas_grids(traced.jaxpr) == [(min(padded, CHUNK),)]


def test_segment_aggregate_schedule_visits_the_hit_pairs():
    """Real visits are the (tile, block) pairs that hold hits, tile-major;
    a tile without hits has none, and the rest of a chunk repeats the
    last pair."""
    import jax.numpy as jnp
    from repro.kernels.segment_aggregate.segment_aggregate import (
        _chunk, _openings)

    block, n_tiles, s, tile_k = 4, 6, 2, 8
    sizes = np.asarray([3, 0, 6, 0, 0, 9])          # 18 sorted hits
    cells = np.concatenate(
        [np.full(z, t * tile_k * s + 1) for t, z in enumerate(sizes)]
        + [np.full(2, n_tiles * tile_k * s)])       # 2 dead lanes
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    tile, opened = _openings(jnp.asarray(cells, jnp.int32), tile_k * s,
                             n_tiles, block)
    n_real = int(opened[-1])
    want = []
    for t in range(n_tiles):
        lo, hi = offsets[t], offsets[t + 1]
        want += [(t, b) for b in range(lo // block, (hi - 1) // block + 1)
                 if hi > lo]
    assert n_real == len(want) == 7
    chunk = 5
    got = []
    for c in range(2):
        tiles, blocks, n_here = (np.asarray(a) for a in _chunk(
            tile, opened, jnp.int32(n_real), c, block, chunk))
        assert int(n_here[0]) == min(chunk, n_real - c * chunk)
        got += list(zip(tiles.tolist(), blocks.tolist()))
    assert got[:n_real] == want
    assert got[n_real:] == [want[-1]] * (2 * chunk - n_real)


def test_segment_aggregate_sparse_call_visits_only_hit_tiles():
    """A few hundred hits in a handful of tiles of a 2^16-key accumulator:
    bit-identical to the xla oracle, every untouched tile unchanged, and
    no more real visits than hit tiles plus hit blocks."""
    import jax.numpy as jnp
    from repro.kernels.segment_aggregate.segment_aggregate import (
        _openings, grid_steps)

    k, s, tile_k, n = 2**16, 5, 128, 300
    rng = np.random.default_rng(11)
    hot = rng.choice(k // tile_k, 6, replace=False)
    keys = (rng.choice(hot, n) * tile_k
            + rng.integers(0, tile_k, n)).astype(np.int32)
    slots = rng.integers(0, s, n).astype(np.int32)
    vals = np.ones((n, 1), np.float32)
    acc = rng.integers(0, 7, (k, s, 1)).astype(np.float32)
    a = np.asarray(segment_aggregate_op(keys, slots, vals, acc,
                                        tile_k=tile_k,
                                        backend="pallas-interpret"))
    b = np.asarray(segment_aggregate_op(keys, slots, vals, acc,
                                        backend="xla"))
    np.testing.assert_array_equal(a, b)
    cold = np.ones(k // tile_k, bool)
    cold[hot] = False
    per_tile = lambda x: x.reshape(k // tile_k, tile_k * s)
    np.testing.assert_array_equal(per_tile(a)[cold], per_tile(acc)[cold])
    cells = np.sort(keys.astype(np.int64) * s + slots)
    _, opened = _openings(jnp.asarray(cells, jnp.int32), tile_k * s,
                          k // tile_k, 384)
    hit_tiles = np.unique(keys // tile_k).size
    hit_blocks = -(-n // 384)
    assert int(opened[-1]) <= hit_tiles + hit_blocks
    assert grid_steps(n, k, tile_k)[0] < (k // tile_k)


def test_segment_aggregate_gauges_set_when_traced():
    import jax
    from repro import obs
    from repro.kernels.segment_aggregate.segment_aggregate import (
        grid_steps, segment_aggregate)

    prev = obs.get()
    o = obs.install(obs.ObsConfig(enabled=True))
    try:
        jax.make_jaxpr(segment_aggregate)(
            np.zeros(1000, np.int32), np.zeros(1000, np.int32),
            np.zeros((1000, 1), np.float32),
            np.zeros((512, 3, 1), np.float32))
    finally:
        obs.set_current(prev)
    visits, dense = grid_steps(1000, 512, 128)
    assert o.registry.gauge("segment_aggregate.visits").value == visits
    assert o.registry.gauge("segment_aggregate.dense_steps").value == dense


def test_window_join_parity():
    rng = np.random.default_rng(2)
    b, k, r, p = 8, 64, 4, 2
    nt = np.sort(rng.integers(100, 300, b)).astype(np.int32)
    ns = rng.integers(0, 2, b).astype(np.int32)
    npay = rng.uniform(0, 40, (b, p)).astype(np.float32)
    st = rng.integers(0, 280, (k, r)).astype(np.int32)
    st[rng.random((k, r)) < 0.3] = -1
    ss = rng.integers(0, 2, (k, r)).astype(np.int32)
    sp = rng.uniform(0, 40, (k, r, p)).astype(np.float32)
    c1, n1 = window_join_op(nt, ns, npay, st, ss, sp, ws=60, tile_k=64,
                            backend="pallas-interpret")
    c2, n2 = window_join_op(nt, ns, npay, st, ss, sp, ws=60, backend="xla")
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    assert int(n1) == int(n2)


def test_flash_attention_parity():
    rng = np.random.default_rng(3)
    q = rng.normal(0, 1, (2, 16, 8)).astype(np.float32)
    k = rng.normal(0, 1, (2, 16, 8)).astype(np.float32)
    v = rng.normal(0, 1, (2, 16, 8)).astype(np.float32)
    a = flash_attention_op(q, k, v, causal=True, blk_q=8, blk_k=8,
                           backend="pallas-interpret")
    b = flash_attention_op(q, k, v, causal=True, backend="xla")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_linear_scan_parity():
    rng = np.random.default_rng(4)
    r = rng.normal(0, 1, (2, 16, 4)).astype(np.float32)
    k = rng.normal(0, 1, (2, 16, 4)).astype(np.float32)
    v = rng.normal(0, 1, (2, 16, 4)).astype(np.float32)
    w = rng.uniform(0.5, 0.99, (2, 16, 4)).astype(np.float32)
    u = rng.normal(0, 1, (2, 4)).astype(np.float32)
    a = linear_scan_op(r, k, v, w, u, chunk=8, backend="pallas-interpret")
    b = linear_scan_op(r, k, v, w, u, backend="xla")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_band_join_counts_backends_agree():
    """core/join's dispatched counting path: both CPU backends equal the
    ref oracle's counts and comparison totals."""
    import jax.numpy as jnp
    from repro.core import tuples as T
    from repro.core.join import band_join_counts, fast_join_init
    from repro.core.windows import WindowSpec

    rng = np.random.default_rng(5)
    K, RING, B, P = 32, 4, 8, 2
    st = fast_join_init(K, RING, P)
    st = st.__class__(
        tau=jnp.asarray(rng.integers(-1, 200, (K, RING)), jnp.int32),
        pay=jnp.asarray(rng.uniform(0, 20, (K, RING, P)), jnp.float32),
        stream=jnp.asarray(rng.integers(0, 2, (K, RING)), jnp.int32),
        n=st.n, c=st.c, comparisons=st.comparisons)
    taus = np.sort(rng.integers(50, 250, B)).astype(np.int32)
    ready = T.make_batch(
        jnp.asarray(taus),
        jnp.asarray(rng.uniform(0, 20, (B, P)), jnp.float32),
        keys=None, source=jnp.asarray(rng.integers(0, 2, B), jnp.int32),
        kmax=1)
    ws = WindowSpec(wa=1, ws=60, wt="single")
    c_x, n_x = band_join_counts(st, ready, ws, band=5.0, backend="xla")
    c_p, n_p = band_join_counts(st, ready, ws, band=5.0,
                                backend="pallas-interpret")
    np.testing.assert_array_equal(np.asarray(c_x), np.asarray(c_p))
    assert int(n_x) == int(n_p)

    # invalid lanes (static-batch padding) match nothing and count nothing
    import dataclasses
    half_valid = jnp.asarray([True] * (B // 2) + [False] * (B // 2))
    masked = dataclasses.replace(ready, valid=half_valid)
    c_m, n_m = band_join_counts(st, masked, ws, band=5.0, backend="xla")
    np.testing.assert_array_equal(np.asarray(c_m)[:B // 2],
                                  np.asarray(c_x)[:B // 2])
    assert not np.asarray(c_m)[B // 2:].any()
    assert int(n_m) < int(n_x)


def test_aggregate_scatter_backends_agree():
    """core/aggregate's dispatched segment-reduce: tick_fast produces the
    same accumulator state on both CPU backends."""
    import jax.numpy as jnp
    from repro.core import tuples as T
    from repro.core.aggregate import count_aggregate, fast_init, tick_fast
    from repro.core.windows import WindowSpec

    rng = np.random.default_rng(6)
    K = 32
    op = count_aggregate(WindowSpec(wa=10, ws=20, wt="multi"), k_virt=K,
                         out_cap=128).resolved()
    taus = np.sort(rng.integers(0, 40, 16)).astype(np.int32)
    keys = rng.integers(0, K, 16).astype(np.int32)
    b = T.make_batch(jnp.asarray(taus), jnp.zeros((16, 1), jnp.float32),
                     keys=jnp.asarray(keys)[:, None], source=None, kmax=1)
    resp = jnp.ones((K,), bool)
    accs = {}
    for backend in ("xla", "pallas-interpret"):
        st, _ = tick_fast(op, "count", fast_init(op), b, resp,
                          backend=backend)
        accs[backend] = np.asarray(st.op_state.zeta["acc"])
    np.testing.assert_allclose(accs["xla"], accs["pallas-interpret"],
                               atol=1e-5)


def test_core_callers_accept_backend():
    """The core integration points run on both CPU backends and agree."""
    import jax.numpy as jnp
    from repro.core import scalegate
    from repro.core import tuples as T

    taus = np.asarray([3, 1, 2, 4, 9, 6, 7, 8], np.int32)
    srcs = np.asarray([0, 1, 0, 1, 0, 1, 0, 1], np.int32)
    batch = T.make_batch(jnp.asarray(taus),
                         jnp.zeros((8, 1), jnp.float32),
                         keys=None, source=jnp.asarray(srcs), kmax=1)
    got = {}
    for backend in ("xla", "pallas-interpret"):
        state = scalegate.init_scalegate(2, capacity=8, kmax=1,
                                         payload_width=1)
        state, out = scalegate.push(state, batch, backend=backend)
        got[backend] = sorted(
            int(t) for t, ok in zip(np.asarray(out.tau),
                                    np.asarray(out.valid)) if ok)
    assert got["xla"] == got["pallas-interpret"] == [1, 2, 3, 4, 6, 7, 8]
