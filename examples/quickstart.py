"""Quickstart: the paper's running example as a STRETCH pipeline.

An A+ computes the longest tweet per hashtag over 1-hour sliding windows
(WA=30 min) with VSN parallelism, then scales from 2 to 4 instances
mid-stream with a <40 ms, zero-state-transfer reconfiguration.

    PYTHONPATH=src:. python examples/quickstart.py
"""

import numpy as np
import jax

from repro import compile_cache
from repro.core.aggregate import longest_aggregate
from repro.core.controller import Reconfiguration, active_mask, balanced_fmu
from repro.core.runtime import VSNPipeline
from repro.core.tuples import make_batch
from repro.core.windows import WindowSpec

K = 32                                  # virtual hashtag keys
MIN = 60 * 1000                         # delta = 1 ms


def tweets(rng, t0, n):
    """Tuples <tau, [len]> with hashtag key sets (f_MK output)."""
    taus = np.sort(t0 + rng.integers(0, 10 * MIN, n)).astype(np.int32)
    keys = rng.integers(0, K, (n, 2)).astype(np.int32)   # up to 2 hashtags
    keys[rng.random((n, 2)) < 0.3] = -1                  # some have fewer
    length = rng.integers(5, 140, (n, 1)).astype(np.float32)
    return make_batch(taus, length, keys=keys, kmax=2), int(taus.max())


def main():
    compile_cache.enable()
    op = longest_aggregate(WindowSpec(wa=30 * MIN, ws=60 * MIN, wt="multi"),
                           k_virt=K, out_cap=256)
    pipe = VSNPipeline(op, n_max=4, n_active=2, stash_cap=64)
    rng = np.random.default_rng(0)

    t0 = 0
    for step in range(6):
        batch, t0 = tweets(rng, t0, 48)
        rc = None
        if step == 3:   # provision two more instances, instantly
            rc = Reconfiguration(epoch=1, n_active=4,
                                 fmu=balanced_fmu(K, 4, 4),
                                 active=active_mask(4, 4))
        o1, o2, switched = pipe.step(batch, reconfig=rc)
        for outs in (o1, o2):
            tau = np.asarray(outs.tau); pay = np.asarray(outs.payload)
            ok = np.asarray(outs.valid)
            for j in range(tau.shape[0]):
                for t, p, v in zip(tau[j], pay[j], ok[j]):
                    if v:
                        print(f"  window closing at {t//MIN:4d} min: "
                              f"hashtag {int(p[0]):2d} longest {int(p[1])} chars")
        if bool(switched):
            print(f"[step {step}] reconfigured 2 -> 4 instances "
                  f"(epoch {int(pipe.epoch.e)}, zero state moved)")
    print("quickstart OK")


if __name__ == "__main__":
    main()
