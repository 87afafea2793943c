"""Meshes + compiled-HLO collective accounting.

Production meshes are never built at import (jax device state stays cold).
``make_stream_mesh`` is the 1-D instance-axis mesh the VSN runtime shards
key blocks over (core.runtime.MeshPipeline); on a laptop/CI host emulate
devices with

    XLA_FLAGS=--xla_force_host_platform_device_count=8

set *before* the first jax import (see tests/test_mesh_runtime.py and the
``multi-device`` CI job).

``collective_bytes`` parses a compiled HLO text and sums the output bytes
of every cross-device collective — the zero-state-transfer witness for the
mesh VSN step (Theorem 3: an ``f_mu`` switch moves tables, never sigma).
"""

from __future__ import annotations

import re

import jax

STREAM_AXIS = "i"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over local devices for tests."""
    return jax.make_mesh((data, model), ("data", "model"))


def make_stream_mesh(n_shards: int = None, axis: str = STREAM_AXIS):
    """1-D mesh over ``n_shards`` local devices for the VSN instance axis
    (defaults to every visible device).

    The axis is ``Auto``: the pipeline places sigma and the replicated
    tables itself and ``shard_map`` states every spec, while ``Explicit``
    axes (``jax.make_mesh``'s default) turn sharding into part of each
    array's type and reject the step's mixed sharded/replicated updates
    (``core.runtime.inject_ctrl``)."""
    n_shards = n_shards or len(jax.devices())
    avail = len(jax.devices())
    if n_shards > avail:
        raise ValueError(
            f"mesh wants {n_shards} devices but only {avail} are visible; "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={n_shards} "
            f"before the first jax import to emulate them on CPU")
    return jax.make_mesh((n_shards,), (axis,),
                         axis_types=(jax.sharding.AxisType.Auto,))


# ---------------------------------------------------------------------------
# Compiled-HLO collective accounting
# ---------------------------------------------------------------------------

COLLECTIVE_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[^=]*=\s*([a-z0-9]+)\[([0-9,]*)\]", re.I)

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
               "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "s16": 2,
               "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}


def collective_bytes(hlo_text: str):
    """Sum output-shape bytes of every collective op in the compiled HLO."""
    per_kind = {}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        kind = m.group(1).lower().removesuffix("-start")
        dt = m.group(2)
        dims = [int(x) for x in m.group(3).split(",") if x]
        n = 1
        for d in dims:
            n *= d
        b = n * DTYPE_BYTES.get(dt, 4)
        per_kind[kind] = per_kind.get(kind, 0) + b
    return per_kind


# host-boundary crossings inside a compiled program: send/recv pairs marked
# as host transfers, infeed/outfeed queues, and host-callback custom-calls
# (io_callback / pure_callback / debug prints all lower to one of these).
HOST_TRANSFER_RE = re.compile(
    r"is_host_transfer=true"
    r"|\b(?:infeed|outfeed)(?:-done|-start)?\("
    r"|custom_call_target=\"[^\"]*callback[^\"]*\"", re.I)


def host_transfer_ops(hlo_text: str):
    """HLO lines that move data across the host boundary *inside* the
    compiled program — the device-residency witness for the persistent
    K-tick drivers (``runtime.*.persistent_hlo``): an empty list proves the
    scan's data lane never leaves the device between ticks (arguments and
    results don't count; they cross once per call by definition)."""
    return [ln.strip() for ln in hlo_text.splitlines()
            if HOST_TRANSFER_RE.search(ln)]
