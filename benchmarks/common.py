"""Shared benchmark scaffolding: timed pipeline drives + CSV rows."""

from __future__ import annotations

import json
import re
import time

import numpy as np
import jax

ROWS = []

CSV_HEADER = ["name", "us_per_call", "derived", "p50_ms", "p99_ms",
              "detect_switch_ms", "detect_recover_ms"]


def emit(name: str, us_per_call: float, derived: str = "", *,
         p50_ms: float = None, p99_ms: float = None,
         detect_switch_ms: float = None, detect_recover_ms: float = None):
    """One result row.  The optional latency columns (tick-latency p50/p99,
    detection→switch latency, and the fault-tolerance twin
    detection→recovered latency, all ms) come from the live-runtime and
    recovery variants; plain rows leave them empty in the CSV."""
    ROWS.append((name, us_per_call, derived, p50_ms, p99_ms,
                 detect_switch_ms, detect_recover_ms))
    extra = "".join(
        f",{k}={v:.2f}" for k, v in [("p50_ms", p50_ms), ("p99_ms", p99_ms),
                                     ("d2s_ms", detect_switch_ms),
                                     ("d2r_ms", detect_recover_ms)]
        if v is not None)
    print(f"{name},{us_per_call:.1f},{derived}{extra}", flush=True)


def failed_rows():
    """Rows that signal a failure: a FAIL marker in the name or derived
    column (e.g. ``outputs_match_static=False``).  SKIP rows don't count."""
    bad = []
    for row in ROWS:
        name, us, derived = row[0], row[1], row[2]
        text = f"{name} {derived}"
        if "FAIL" in text or "=False" in text:
            bad.append((name, us, derived))
    return bad


def write_csv(path: str):
    import csv

    with open(path, "w", newline="") as f:
        w = csv.writer(f)   # quotes the comma-laden derived column
        w.writerow(CSV_HEADER)
        for name, us, derived, p50, p99, d2s, d2r in ROWS:
            w.writerow([name, f"{us:.1f}", derived]
                       + [("" if v is None else f"{v:.3f}")
                          for v in (p50, p99, d2s, d2r)])


TPUT_RE = re.compile(r"([0-9][0-9.e+]*)\s*t/s")


def write_bench_json(path: str, query: str, rows, config: dict):
    """Perf-trajectory artifact (``BENCH_q<id>.json``): the run config plus
    this query's result rows.  ``tput_tps`` is parsed from the first
    ``<N> t/s`` figure in the derived column when present, else derived
    from us_per_call; rows without either leave it null."""
    out_rows = []
    for name, us, derived, p50, p99, d2s, d2r in rows:
        m = TPUT_RE.search(derived or "")
        tput = (float(m.group(1)) if m
                else (1e6 / us if us else None))
        out_rows.append(dict(name=name, us_per_call=us, tput_tps=tput,
                             p50_ms=p50, p99_ms=p99, detect_switch_ms=d2s,
                             detect_recover_ms=d2r, derived=derived))
    with open(path, "w") as f:
        json.dump(dict(query=query, config=config, rows=out_rows), f,
                  indent=2)
        f.write("\n")


def run_device_resident_bench(make_stream, n_sources: int, n_leaves: int,
                              make_pipe, *, tick: int, super_batch: int = 8,
                              queue_cap: int = 4, oracle_cap: int = None,
                              reps: int = 3):
    """Device-resident hot path vs the per-tick host-merge baseline on the
    identical multihost stream (q1/q3 shared harness).

    * baseline — ``RootMerge`` on host (one watermark sync per merge
      round) feeding one compiled step dispatch per tick;
    * device   — fused stacked-leaf root merge (``RootMerge(device=True)``)
      feeding the persistent compiled K-tick scan (``super_batch=K``).

    The gated comparison isolates the *hot path* the PR changes: the leaf
    rounds are prerecorded once (leaf ingest is byte-identical in both
    variants and, on a single-core CPU host, dominates end-to-end time),
    then each variant's merge→step loop runs once from fresh state for the
    parity outputs and ``reps`` more times on the warm executables for the
    best-of timing.  An end-to-end async pass (full ``IngestTier`` +
    ``AsyncStreamRuntime``) runs last as the informational whole-system
    rows.  Single-core CPU caveat: XLA "device" compute shares the one
    core with ingest, so the tick math itself is not accelerated — the
    hot-path speedup here measures what the fused merge + persistent scan
    remove (per-tick dispatch, watermark syncs, staging); on a real
    accelerator the same code path also overlaps host/device work.

    Returns ``(res, parity)``: ``res["hot"]`` (host_tps/dev_tps/speedup/
    fill), ``res["host"|"device"]["report"]`` (end-to-end), and the
    exact-output gates (device-merged stream vs single-ScaleGate oracle,
    host-variant vs device-variant output multisets, device-variant vs a
    synchronous replay of its own merged stream)."""
    from repro.core.async_runtime import AsyncStreamRuntime
    from repro.ingest import IngestTier, collect_tuples, single_gate_stream
    from repro.ingest import leaf as L
    from repro.ingest.root import RootMerge, bucket
    from repro.ingest.tier import SourcePartitioner
    from repro.io import NullSink
    from repro.io.sinks import flatten_outputs

    batches = list(make_stream())
    kmax, pw = batches[0].kmax, batches[0].payload_width
    part = SourcePartitioner(n_sources, range(n_leaves))

    # prerecord the leaf rounds (identical input to both merge variants)
    gates = {l: L.LeafGate(l, n_sources, part.owned_mask(l), tick, kmax, pw)
             for l in part.leaves}
    rounds = []
    for r, b in enumerate(batches):
        b_np = L.batch_to_np(b)
        keep = b_np["valid"]
        leaf_of = part.assignment[np.clip(b_np["source"], 0, n_sources - 1)]
        rounds.append([gates[l].push_round(
            r, {f: b_np[f][keep & (leaf_of == l)] for f in L.FIELDS})
            for l in part.leaves])
    fin = []
    for l in part.leaves:
        gates[l].flush_all()
        fin.append(gates[l].push_round(len(batches), None, final=True))
    rounds.append(fin)
    ntup = sum(int((np.asarray(b.valid) & ~np.asarray(b.is_control)).sum())
               for b in batches)

    # identical fixed-shape output contract for both variants: the device
    # path reserves one chunk per leaf (cap + n_leaves*chunk lanes), so the
    # host baseline buckets from the same floor — otherwise the comparison
    # measures lane-count padding (every lane costs real compute per tick
    # downstream), not the merge/dispatch/sync overhead the PR removes
    chunk = bucket(tick)

    def make_root(device):
        return RootMerge(max(2 * n_leaves, n_leaves + 4), 2 * tick, kmax,
                         pw, part.leaves,
                         out_pad=(tick if device else n_leaves * chunk),
                         device=device, check_every=8)

    def drive_host(pipe, root, collect=None):
        for outs in rounds:
            rb = root.push(outs)
            o1, o2, sw, il = pipe.step_staged(rb)
            bool(sw), np.asarray(il)      # control-lane syncs, as in live
            if collect is not None:
                collect.append((rb, o1, o2))

    fill = [0, 0]                         # dispatches, ticks dispatched

    def drive_device(pipe, root, collect=None):
        group, key = [], [None]

        def flush():
            if not group:
                return
            out = pipe.run_persistent_staged(
                pipe.stage_super(group, super_batch))
            bool(out.switched.any()), np.asarray(out.inst_load.sum(axis=0))
            fill[0] += 1
            fill[1] += len(group)
            if collect is not None:
                collect.append((list(group), out))
            del group[:]

        for outs in rounds:
            rb = root.push(outs)
            k2 = (rb.batch, rb.kmax, rb.payload_width)
            if group and k2 != key[0]:
                flush()                   # shape change: flush the group
            group.append(rb)
            key[0] = k2
            if len(group) == super_batch:
                flush()
        flush()

    # fresh-state pass: compiles everything + yields the parity outputs
    pipe_h, pipe_d = make_pipe(), make_pipe()
    coll_h, coll_d = [], []
    drive_host(pipe_h, make_root(False), coll_h)
    drive_device(pipe_d, make_root(True), coll_d)
    host_outs = sorted(sum((flatten_outputs(o1) + flatten_outputs(o2)
                            for _, o1, o2 in coll_h), []))
    dev_outs = sorted(sum(
        (flatten_outputs(o.outs_pre) + flatten_outputs(o.outs_post)
         for _, o in coll_d), []))
    dev_emitted = [rb for grp, _ in coll_d for rb in grp]

    pipe_s = make_pipe()                  # sequential replay oracle
    sync_outs = []
    for rb in dev_emitted:
        o1, o2, _ = pipe_s.step(rb)
        sync_outs += flatten_outputs(o1) + flatten_outputs(o2)
    oracle = single_gate_stream(list(make_stream()), n_sources,
                                cap=oracle_cap or 3 * tick)
    parity = dict(
        tier=collect_tuples(dev_emitted) == collect_tuples(oracle),
        pipeline=host_outs == dev_outs,
        sync=sorted(sync_outs) == dev_outs,
    )

    # timed reps on the warm executables (fresh roots, best-of timing —
    # single-core scheduler noise makes mean/median unstable)
    fill[0] = fill[1] = 0
    hs, ds = [], []
    for _ in range(reps):
        root = make_root(False)
        t0 = time.perf_counter()
        drive_host(pipe_h, root)
        hs.append(ntup / (time.perf_counter() - t0))
        root = make_root(True)
        t0 = time.perf_counter()
        drive_device(pipe_d, root)
        ds.append(ntup / (time.perf_counter() - t0))
    res = {"hot": dict(host_tps=max(hs), dev_tps=max(ds),
                       speedup=max(ds) / max(max(hs), 1e-9),
                       fill=fill[1] / max(fill[0], 1), reps=reps,
                       ntup=ntup)}

    # end-to-end async pass (informational): full tier + async runtime
    for name, device, sb, pipe in (("host", False, 1, pipe_h),
                                   ("device", True, super_batch, pipe_d)):
        tier = IngestTier(make_stream(), n_sources, n_leaves,
                          worker="thread", leaf_cap=tick,
                          root_cap=2 * tick,
                          out_pad=(tick if device else n_leaves * chunk),
                          root_device=device)
        rt = AsyncStreamRuntime(pipe, tier, sink=NullSink(),
                                queue_cap=queue_cap, super_batch=sb)
        res[name] = dict(report=rt.run())
    return res, parity


def run_ingest_bench(batches, n_sources: int, n_leaves: int, *, tick: int,
                     oracle_cap: int = None):
    """Shared multihost-ingest harness (q1/q3): root-merge throughput per
    leaf count in {1, n_leaves} (warm-jit pass then timed pass), plus a
    recorded pass checked tuple-for-tuple against the single-ScaleGate
    oracle.  Returns ``(tput_by_leaves, tier_ticks, tier_parity_ok)``."""
    from repro.ingest import (IngestTier, collect_tuples,
                              single_gate_stream)

    kw = dict(worker="thread", leaf_cap=tick, root_cap=2 * tick,
              out_pad=2 * tick)
    tput = {}
    for leaves in sorted({1, n_leaves}):
        list(IngestTier(batches, n_sources, leaves, **kw))   # warm jits
        tier = IngestTier(batches, n_sources, leaves, **kw)
        t0 = time.perf_counter()
        list(tier)
        tput[leaves] = tier.stats().tuples_out / (time.perf_counter() - t0)
    tier = IngestTier(batches, n_sources, n_leaves, record=True, **kw)
    tier_ticks = list(tier)
    oracle = single_gate_stream(batches, n_sources,
                                cap=oracle_cap or 3 * tick)
    ok = collect_tuples(tier_ticks) == collect_tuples(oracle)
    return tput, tier_ticks, ok


def run_recovery_bench(name: str, cfg, batches, *, mode: str = "stop",
                       crash_after: int = 6, crash_mid_save: bool = True):
    """Kill-and-restore as a measured bench row: runs
    ``repro.launch.recovery.kill_restore_drill`` on an ``api.RuntimeConfig``
    stack (victim → latest complete manifest → identical rebuilt stack →
    replay) and emits one parity-gated row whose ``detect_recover_ms``
    column is the detection→recovered latency — the fault-tolerance twin of
    the detection→switch column.  ``exactly_once=False`` in the derived
    text makes it a FAIL row (``failed_rows`` → nonzero bench exit)."""
    from repro.launch.recovery import kill_restore_drill

    rep = kill_restore_drill(cfg, batches, mode=mode,
                             crash_after=crash_after,
                             crash_mid_save=crash_mid_save)
    emit(name, rep.detect_to_recover_ms * 1e3,
         f"restored_step={rep.restored_step}, {rep.n_committed} committed "
         f"+ {rep.n_replayed} replayed, exactly_once={rep.parity}",
         detect_recover_ms=rep.detect_to_recover_ms)
    return rep


def _amplified_source(src, events_per_tick: int):
    """Detail-event pressure for the sampled variant: fire
    ``events_per_tick`` extra flight events per batch on the ingest thread
    — a ~10x event rate the sampler must absorb without widening the
    overhead gate.  Only ring detail thins; every counter still counts."""
    from repro import obs
    for b in src:
        for i in range(events_per_tick):
            obs.event("synthetic_load", seq=i)
        yield b


def run_obs_overhead_bench(make_pipe, make_source, warm, *,
                           queue_cap: int = 4, reps: int = 3,
                           synthetic_events: int = 10):
    """Observability cost gate: the identical async run under four obs
    settings — fully off (baseline), metrics+flight with tracing disabled
    (the always-on tier, gated <2%), full span tracing (gated <10%), and
    full tracing under adaptive head sampling while the source fires
    ``synthetic_events`` extra flight events per tick (~10x the normal
    event rate; gated <2% — sampling must make tracing always-on cheap).

    Each variant gets a fresh pipeline compiled outside the timed window
    (``pipe.step(warm)``) and ``reps`` full runs; best-of throughput is
    compared (single-core scheduler noise makes means unstable).  The
    previously installed global ``Obs`` is restored afterwards, whatever
    happens — the bench must not leave its instrumentation behind.

    Returns per-variant tps, the relative overheads, ``parity`` (exact
    output-set equality across all variants — obs must never perturb
    results), and ``counters_exact`` (``bus.ticks``/``bus.tuples`` totals
    bit-identical between the trace and sampled runs: sampling thins
    detail records only, never accounting)."""
    from repro import obs
    from repro.core.async_runtime import AsyncStreamRuntime

    prev = obs.get()
    tps, results, counters = {}, {}, {}
    sampler_snap = {}
    try:
        for name, cfg, amplify in (
                ("off", None, 0),
                ("metrics", obs.ObsConfig(enabled=True, trace=False), 0),
                ("trace", obs.ObsConfig(enabled=True, trace=True), 0),
                ("sampled", obs.ObsConfig(
                    enabled=True, trace=True,
                    event_sample=1.0 / 64.0, span_sample=1.0 / 16.0,
                    event_budget_per_s=2000.0), synthetic_events)):
            obs.set_current(obs.Obs(cfg) if cfg is not None else None)
            best = 0.0
            for _ in range(reps):
                pipe = make_pipe()
                pipe.step(warm)               # compile outside the window
                src = make_source()
                if amplify:
                    src = _amplified_source(src, amplify)
                rt = AsyncStreamRuntime(pipe, src, queue_cap=queue_cap)
                rep = rt.run()
                best = max(best, rep.throughput_tps)
            tps[name] = best
            results[name] = rt.sink.results()
            o = obs.get()
            if o is not None and cfg.trace:
                counters[name] = {
                    k: v for k, v in o.snapshot()["counters"].items()
                    if k in ("bus.ticks", "bus.tuples")}
                if o.sampler is not None:
                    sampler_snap = o.sampler.snapshot()
    finally:
        obs.set_current(prev)
    base = max(tps["off"], 1e-9)
    return dict(
        base_tps=tps["off"], metrics_tps=tps["metrics"],
        trace_tps=tps["trace"], sampled_tps=tps["sampled"],
        metrics_overhead=1.0 - tps["metrics"] / base,
        trace_overhead=1.0 - tps["trace"] / base,
        sampled_overhead=1.0 - tps["sampled"] / base,
        counters_exact=(counters["trace"] == counters["sampled"]),
        sampler=sampler_snap,
        parity=(results["off"] == results["metrics"]
                == results["trace"] == results["sampled"]))


def time_fn(fn, *args, warmup=2, iters=5):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6, out
