"""Compile every registered kernel for a described TPU v5e chip.

The TPU compiler ships with libtpu and compiles for a chip that is
described, not attached (``jax.experimental.topologies``), so this file
runs the full Mosaic pipeline the chip runs — and refuses what the chip
would refuse (scalar stores to VMEM, blocks that break the (8, 128) rule,
primitives Mosaic cannot lower, VMEM overruns) — at no chip time.  The
Pallas interpreter the rest of the suite uses accepts all of those.

Each case compiles one kernel at the per-block shapes of the deployment
that runs it (``chip_smoke.py``'s q1 wordcount for the merges and
``segment_aggregate``; the q3 join; qwen3-14b and rwkv6-7b head widths),
with a short grid.  The topology is described inside a fixture, never at
import: only one process may load the TPU library, and pytest-xdist
workers each import every test file.
"""

import functools
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


def _cases():
    """name -> (kernel entry, [(shape, dtype), ...])."""
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
        # q1 wordcount blocks (128 keys x 4 slots x 512 hits), 8 x 4 grid
        "segment_aggregate": (
            functools.partial(segment_aggregate, tile_k=128),
            [((2048,), I32), ((2048,), I32), ((2048, 1), F32),
             ((1024, 4, 1), F32)]),
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
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem is not None and mem.generated_code_size_in_bytes > 0
