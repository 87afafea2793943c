"""Compile every registered kernel for a described TPU v5e chip.

The TPU compiler ships with libtpu and compiles for a chip that is
described, not attached (``jax.experimental.topologies``), so this file
runs the full Mosaic pipeline the chip runs — and refuses what the chip
would refuse (scalar stores to VMEM, blocks that break the (8, 128) rule,
primitives Mosaic cannot lower, VMEM overruns) — at no chip time.  The
Pallas interpreter the rest of the suite uses accepts all of those.

Each case compiles one kernel at the per-block shapes of the deployment
that runs it (``chip_smoke.py``'s q1 wordcount for the merges; the q3
join; qwen3-14b and rwkv6-7b head widths), with a short grid.
``segment_aggregate`` compiles at the whole call of the benchmark's q1
deployment, its shapes read from the traced pipeline step, and at one
key block's call of the q1 paircount deployment sharded over four chips
(its hit-tile schedule at 2^27 keys), read from the traced tick of one
shard.  The topology
is described inside a fixture, never at import: only one process may load
the TPU library, and pytest-xdist workers each import every test file.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.linear_scan.linear_scan import linear_scan
from repro.kernels.scalegate_merge.scalegate_merge import (
    scalegate_merge, scalegate_merge_stacked)
from repro.kernels.segment_aggregate.segment_aggregate import \
    segment_aggregate
from repro.kernels.window_join.window_join import window_join

F32, I32 = jnp.float32, jnp.int32
CONFIGS = os.path.join(os.path.dirname(__file__), os.pardir, "perfbench",
                       "configs")
Q1_CONFIG = os.path.join(CONFIGS, "q1-wordcount.json")
PAIRCOUNT_CONFIG = os.path.join(CONFIGS, "q1-paircount-mesh4.json")


def _root_tick(cfg, kmax, width):
    """Shape of one tick out of the device root merge of ``cfg``."""
    from repro.core import tuples as T
    from repro.ingest.root import RootMerge

    leaves = cfg.effective_max_leaves
    root = RootMerge(leaves, cfg.root_cap, kmax, width,
                     range(cfg.ingest_hosts), out_pad=cfg.out_pad,
                     device=True)
    rows = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((cfg.ingest_hosts,) + a.shape,
                                       a.dtype),
        T.empty_batch(root.chunk, kmax, width))
    _, tick = jax.eval_shape(root._push_stacked, root.state, rows,
                             jnp.zeros((leaves,), I32),
                             jnp.zeros((leaves,), bool))
    return tick


def _all_eqns(jaxpr):
    from repro.kernels.lowering import _sub_jaxprs
    yield from jaxpr.eqns
    for sub in _sub_jaxprs(jaxpr):
        yield from _all_eqns(sub)


def _segment_aggregate_call(jaxpr):
    """Operand shapes of the innermost jitted call that holds the kernel
    (the wrapper; the kernel itself sits in its chunk loop), the same at
    every call site."""
    from repro.kernels.lowering import _as_jaxpr

    def holds(j):
        return any(e.primitive.name == "pallas_call"
                   and e.params.get("name") == "segment_aggregate"
                   for e in _all_eqns(j))

    def calls(j):
        for eqn in j.eqns:
            subs = [_as_jaxpr(item) for val in eqn.params.values()
                    for item in (val if isinstance(val, (list, tuple))
                                 else [val])]
            deeper = [c for sub in subs if sub is not None
                      for c in calls(sub)]
            inner = _as_jaxpr(eqn.params.get("jaxpr"))
            if deeper:
                yield from deeper
            elif (inner is not None and eqn.primitive.name != "pallas_call"
                  and holds(inner)):
                yield [(v.aval.shape, v.aval.dtype) for v in eqn.invars]

    found = list(calls(jaxpr))
    assert found, "the step calls no segment_aggregate kernel"
    assert all(c == found[0] for c in found), found
    return found[0]


def _q1_segment_aggregate_shapes():
    """The (keys, slots, vals, acc) shapes of the ``segment_aggregate``
    call in the benchmark's q1 step: the pipeline the configuration
    builds, traced over a super-batch of the device root merge's output,
    and the jitted call that wraps the kernel read off its jaxpr."""
    from repro.api import RuntimeConfig, make_pipeline
    from repro.core import tuples as T
    from repro.core.runtime import _pad_stack

    with open(Q1_CONFIG) as f:
        d = json.load(f)
    cfg = RuntimeConfig.from_json({**d, "backend": "pallas"})
    kmax, width = d["words_per_tweet"], 1
    tick = _root_tick(cfg, kmax, width)
    pipe = make_pipeline(cfg)
    pipe.ensure_gate_for(kmax, width)
    ctrl = T.empty_batch(pipe.op.n_inputs, kmax, width)
    stack = jax.eval_shape(
        lambda *ticks: _pad_stack(pipe.op.n_inputs, cfg.super_batch, *ticks),
        *[tick] * cfg.super_batch)
    step = jax.make_jaxpr(pipe._persistent_fn)(
        pipe.sg, pipe.epoch, pipe.sigma, stack, ctrl, jnp.zeros((), I32),
        pipe.epoch.fmu, pipe.epoch.active)
    return _segment_aggregate_call(step.jaxpr)


def _paircount_shard_segment_aggregate_shapes():
    """The same for one key block of the q1 paircount deployment: the
    pipeline tick one shard runs (``vsn.pipeline_tick`` over the shard's
    local tick), traced on abstract state, since the deployment's state
    does not fit this host."""
    import dataclasses

    from repro.api import RuntimeConfig, make_op
    from repro.core import elastic, scalegate, vsn
    from repro.core import tuples as T
    from repro.core.aggregate import fast_init

    with open(PAIRCOUNT_CONFIG) as f:
        d = json.load(f)
    cfg = RuntimeConfig.from_json({**d, "backend": "pallas"})
    words, dist = d["words_per_tweet"], d["pair_dist"]
    kmax, width = sum(min(dist, words - 1 - i) for i in range(words)), 1
    assert kmax == 12
    rows = cfg.k_virt // cfg.mesh_devices
    op = make_op(cfg).resolved()
    tick_l = vsn.fast_agg_local_tick(op, "count", "pallas")(0, rows)
    op_l = vsn.localize_op(op, 0, rows)
    sigma = jax.eval_shape(lambda: fast_init(op_l))
    sg = scalegate.init_scalegate(op.n_inputs, cfg.stash_cap, kmax, width)
    code = jnp.zeros((), I32)
    epoch = dataclasses.replace(
        elastic.init_epoch(code, jnp.ones((cfg.n_max,), bool)),
        fmu_next=code)
    incoming = jax.eval_shape(
        T.concat, _root_tick(cfg, kmax, width),
        T.empty_batch(op.n_inputs, kmax, width))
    step = jax.make_jaxpr(lambda sigma, incoming: vsn.pipeline_tick(
        sg, epoch, sigma, incoming, code, epoch.active,
        lambda s, r, e: tick_l(s, r)))(sigma, incoming)
    shapes = _segment_aggregate_call(step.jaxpr)
    assert shapes[3][0] == (rows, 5, 1), shapes
    return shapes


def _cases():
    """name -> (kernel entry, [(shape, dtype), ...] or a function that
    resolves them)."""
    return {
        # q1 pipeline tick: 1024 stash + 3 x 8192 root rows + 4 ctrl lanes,
        # padded by the kernel to 32768 lanes
        "scalegate_merge": (
            functools.partial(scalegate_merge, n_sources=4),
            [((25604,), I32)] * 3),
        # q1 root round: 8192 stash lanes + 2 leaf rows of 8192, 6 leaf slots
        "scalegate_merge_stacked": (
            scalegate_merge_stacked,
            [((3, 8192), I32)] * 3 + [((6,), I32)]),
        # the benchmark's q1 call, from its step (shapes resolved in the
        # test): 65,536 keys x 5 slots, 25,604 lanes x 6 keys x 3 windows
        "segment_aggregate": (
            functools.partial(segment_aggregate, tile_k=128),
            _q1_segment_aggregate_shapes),
        # one key block of q1 paircount on four chips: 2^27 keys x 5 slots,
        # 25,604 lanes x 12 pair keys x 3 windows
        "segment_aggregate_paircount_shard": (
            functools.partial(segment_aggregate, tile_k=128),
            _paircount_shard_segment_aggregate_shapes),
        # q3 ScaleJoin: 256-tuple ticks, ring 32, 4 payload attributes
        "window_join": (
            functools.partial(window_join, ws=500, band=10.0, n_attrs=2,
                              tile_k=128),
            [((256,), I32), ((256,), I32), ((256, 4), F32),
             ((512, 32), I32), ((512, 32), I32), ((512, 32, 4), F32)]),
        # qwen3-14b head width 128, 128 x 128 blocks
        "flash_attention": (
            functools.partial(flash_attention, causal=True, window=None,
                              blk_q=128, blk_k=128),
            [((2, 256, 128), F32)] * 3),
        # rwkv6-7b head width 64, 64-step chunks, with the bonus term
        "linear_scan": (
            functools.partial(linear_scan, chunk=64),
            [((2, 128, 64), F32)] * 4 + [((2, 64), F32)]),
    }


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # otherwise the compiler writes its logs under /tmp
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:          # no libtpu, or it is held elsewhere
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip (every later hit warns and
    recompiles), so these compiles bypass it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.mark.parametrize("name", sorted(_cases()))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes = _cases()[name]
    if callable(shapes):
        shapes = shapes()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem is not None and mem.generated_code_size_in_bytes > 0
