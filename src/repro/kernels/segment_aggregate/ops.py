"""Backend-dispatched public entry points for the segment_aggregate kernel."""

import functools

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.segment_aggregate.ref import segment_aggregate_ref
from repro.kernels.segment_aggregate.segment_aggregate import (
    BLOCK_N, pallas_specs, segment_aggregate)


def _xla(keys, slots, vals, acc, *, tile_k=None):
    del tile_k                      # a Pallas tiling knob; XLA fuses freely
    return segment_aggregate_ref(keys, slots, vals, acc)


dispatch.register_kernel("segment_aggregate",
                         pallas=segment_aggregate, xla=_xla)


def _lowering_case():
    from repro.kernels import lowering
    # two key tiles x two hit blocks: a visit grid of three
    n, w, k, s, tile_k = 1024, 2, 256, 4, 128
    return lowering.KernelCase(
        "segment_aggregate",
        fn=functools.partial(segment_aggregate, tile_k=tile_k),
        args=(jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32),
              jnp.zeros((n, w), jnp.float32),
              jnp.zeros((k, s, w), jnp.float32)),
        specs=pallas_specs(n, w, k, s, tile_k, block_n=BLOCK_N))


dispatch.register_lint("segment_aggregate", _lowering_case)


@functools.partial(jax.jit, static_argnames=("tile_k", "backend"))
def _impl(keys, slots, vals, acc, *, tile_k, backend):
    fn = dispatch.lookup("segment_aggregate", backend)
    return fn(keys, slots, vals, acc, tile_k=tile_k)


def segment_aggregate_op(keys, slots, vals, acc, *, tile_k=128, backend=None):
    return _impl(keys, slots, vals, acc, tile_k=tile_k,
                 backend=dispatch.resolve(backend))


segment_aggregate_ref_op = jax.jit(segment_aggregate_ref)
