"""Ingest-tier driver: the hierarchical multi-host ScaleGate, standalone.

    PYTHONPATH=src python -m repro.launch.ingest_tier --leaves 3 \
        --sources 6 --ticks 24 --join-at 6 --leave-at 14

Streams a multi-source Q1-style workload through ``repro.ingest.IngestTier``
(N leaf ScaleGates, each an ingest worker merging a disjoint source subset,
feeding the root merge) and verifies, live:

* exact output-set parity with the single-ScaleGate oracle — including
  across a mid-stream ``add_host`` (``--join-at``) and ``remove_host``
  (``--leave-at``);
* the merged ready stream is totally ordered and the root watermark never
  regresses (checked every round inside ``RootMerge``);
* membership changes move zero tuple state — only Lemma-3 gammas — with
  measured attach/detach latency;
* stash overflow at either level is surfaced, never silent.

``--worker`` selects the leaf execution vehicle (thread | process |
inline); ``--pipeline`` additionally drives the merged stream through a
``VSNPipeline`` via ``AsyncStreamRuntime`` (the tier as a drop-in live
source upstream of ``stage()``).
"""

import argparse
import sys
import time

import numpy as np

from repro import compile_cache
from repro.data import datagen
from repro.ingest import (IngestTier, collect_tuples, emitted_taus,
                          single_gate_stream)

K_VIRT = 128


def make_stream(args):
    rng = np.random.default_rng(args.seed)
    return list(datagen.tweets(
        rng, n_ticks=args.ticks, tick=args.tick, words_per_tweet=3,
        vocab=2000, k_virt=K_VIRT, rate_per_tick=50,
        n_sources=args.sources))


def main(argv=None):
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--leaves", type=int, default=3)
    ap.add_argument("--sources", type=int, default=6)
    ap.add_argument("--ticks", type=int, default=24)
    ap.add_argument("--tick", type=int, default=64, help="tuples per tick")
    ap.add_argument("--worker", default="thread",
                    choices=["thread", "process", "inline"])
    ap.add_argument("--leaf-cap", type=int, default=128)
    ap.add_argument("--root-cap", type=int, default=256)
    ap.add_argument("--join-at", type=int, default=None,
                    help="add an ingest host before this data tick")
    ap.add_argument("--leave-at", type=int, default=None,
                    help="remove leaf 0 before this data tick")
    ap.add_argument("--pipeline", action="store_true",
                    help="also drive the merged stream through a "
                         "VSNPipeline via AsyncStreamRuntime")
    ap.add_argument("--seed", type=int, default=11)
    args = ap.parse_args(argv)

    batches = make_stream(args)
    tier = IngestTier(batches, args.sources, args.leaves,
                      worker=args.worker, leaf_cap=args.leaf_cap,
                      root_cap=args.root_cap, record=args.pipeline)
    if args.join_at is not None:
        new_leaf = tier.add_host(at_tick=args.join_at)
        print(f"# scheduled add_host -> leaf {new_leaf} at tick "
              f"{args.join_at}")
    if args.leave_at is not None:
        tier.remove_host(0, at_tick=args.leave_at)
        print(f"# scheduled remove_host(0) at tick {args.leave_at}")

    t0 = time.perf_counter()
    outs = list(tier)
    dt = time.perf_counter() - t0
    st = tier.stats()
    print(f"[ingest] {st.summary()}")
    print(f"[ingest] root-merge throughput {st.tuples_out / max(dt, 1e-9):.0f} t/s "
          f"over {dt:.2f}s ({args.worker} workers)")

    taus = emitted_taus(outs)
    assert (np.diff(taus) >= 0).all(), "ready stream lost total order"
    oracle = single_gate_stream(batches, args.sources,
                                cap=args.root_cap + args.leaf_cap)
    same = collect_tuples(outs) == collect_tuples(oracle)
    print(f"[ingest] output set == single-ScaleGate oracle: {same} "
          f"({st.tuples_out} tuples, watermark monotone, "
          f"{len(st.attach_ms)} joins / {len(st.detach_ms)} leaves)")
    assert same, "hierarchical ingest diverged from the flat oracle"

    if args.pipeline:
        from repro.core.aggregate import count_aggregate
        from repro.core.async_runtime import AsyncStreamRuntime
        from repro.core.runtime import VSNPipeline
        from repro.core.windows import WindowSpec

        op = count_aggregate(WindowSpec(wa=500, ws=1000, wt="multi"),
                             k_virt=K_VIRT, out_cap=1024, extra_slots=2,
                             n_inputs=args.sources)
        pipe = VSNPipeline(op, n_max=8, n_active=4,
                           stash_cap=args.root_cap + args.leaf_cap)
        tier2 = IngestTier(batches, args.sources, args.leaves,
                           worker=args.worker, leaf_cap=args.leaf_cap,
                           root_cap=args.root_cap, out_pad=2 * args.tick)
        rt = AsyncStreamRuntime(pipe, tier2, queue_cap=4)
        rep = rt.run()
        print(f"[ingest->pipeline] {rep.summary()}")
    print("ingest tier OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
