"""Mesh streaming: the quickstart A+ on a real device mesh.

The same longest-tweet-per-hashtag pipeline as examples/quickstart.py, but
executed by ``MeshPipeline``: sigma sharded over the devices in fixed key
blocks, ticks ingested in batched stacks (one compiled shard_map call for
T ticks), and a mid-stream reconfiguration that swaps only the replicated
f_mu/active tables — the compiled step moves zero bytes of state between
devices, which this example prints from the compiled HLO.

Run with emulated devices (the flag must precede the first jax import —
this script sets it for you):

    PYTHONPATH=src:. python examples/mesh_streaming.py [n_devices]
"""

import os
import sys

N_DEV = int(sys.argv[1]) if len(sys.argv) > 1 else 4
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={N_DEV}").strip()

import numpy as np
import jax

from repro import compile_cache
from repro.core.aggregate import longest_aggregate
from repro.core.controller import Reconfiguration, active_mask, balanced_fmu
from repro.core.runtime import MeshPipeline
from repro.core.tuples import make_batch
from repro.core.windows import WindowSpec
from repro.launch.mesh import make_stream_mesh

K = 32                                  # virtual hashtag keys
MIN = 60 * 1000                         # delta = 1 ms


def tweets(rng, t0, n):
    taus = np.sort(t0 + rng.integers(0, 10 * MIN, n)).astype(np.int32)
    keys = rng.integers(0, K, (n, 2)).astype(np.int32)
    keys[rng.random((n, 2)) < 0.3] = -1
    length = rng.integers(5, 140, (n, 1)).astype(np.float32)
    return make_batch(taus, length, keys=keys, kmax=2), int(taus.max())


def main():
    compile_cache.enable()
    op = longest_aggregate(WindowSpec(wa=30 * MIN, ws=60 * MIN, wt="multi"),
                           k_virt=K, out_cap=256)
    mesh = make_stream_mesh(N_DEV)
    print(f"mesh: {N_DEV} devices, {K // N_DEV} keys per shard")
    pipe = MeshPipeline(op, mesh, stash_cap=64, mode="general",
                        n_max=4, n_active=2)
    rng = np.random.default_rng(0)

    # batched ingest: stack 3 ticks, scan them in one compiled call
    t0 = 0
    stack = []
    for _ in range(3):
        b, t0 = tweets(rng, t0, 48)
        stack.append(b)
    o1, o2, _ = pipe.run(stack)
    n_out = int(np.asarray(o1.valid).sum() + np.asarray(o2.valid).sum())
    print(f"ticks 0-2 (one shard_map call): {n_out} window outputs")

    # scale 2 -> 4 mid-stream: tables swap, sigma rows stay put
    rc = Reconfiguration(epoch=1, n_active=4, fmu=balanced_fmu(K, 4, 4),
                         active=active_mask(4, 4))
    b, t0 = tweets(rng, t0, 48)
    _, _, switched = pipe.step(b, reconfig=rc)
    b, t0 = tweets(rng, t0, 48)
    _, _, switched2 = pipe.step(b)
    print(f"reconfig 2->4: switched={bool(switched) or bool(switched2)}, "
          f"table bytes={pipe.switch_bytes()}")
    coll = pipe.collective_bytes()
    print(f"cross-device state transfer (compiled HLO collectives): "
          f"{sum(coll.values())} B {coll or ''}")
    assert sum(coll.values()) == 0


if __name__ == "__main__":
    main()
