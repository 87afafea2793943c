#!/usr/bin/env python3
"""Run the STRETCH stream path end to end on a TPU and check its answers.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the same stream on a 4-chip mesh

The deployment is the paper's q1 wordcount (§8.1,
``benchmarks/q1_wordcount.py``) at a size its users would call real:
Zipf-1.3 tweets of 6 words over a 65,536-word vocabulary mapped to 65,536
virtual keys, counted over sliding windows (WA = 1 s, WS = 2 s); 8,192
tweets (49,152 key hits) per tick; 4 sources into 2 ingest leaves and the
fused root merge, all on the host CPU; the mesh pipeline with 8-tick
persistent scans; 16 instances at most, scaled from 8 to 16 mid-stream.
It runs through ``repro.api.build_runtime`` like any user, then checks
that

* every window the run closed equals a plain numpy windowed count of the
  same generated events (independent of ``repro.core``), with no stash,
  output buffer or window ring overflowing anywhere;
* at least one reconfiguration was injected mid-stream and switched;
* one chip: the compiled persistent step calls the ``segment_aggregate``
  and ``scalegate_merge`` kernels as TPU custom calls, and the ingest
  tier's gates (root and leaves) live on the host's CPU device;
* ``--four-chips``: sigma's key blocks sit on 4 distinct devices and the
  compiled step moves 0 bytes between them.  This option runs that path
  and its reference only.

It runs only on a TPU with the compiled Pallas kernels: anywhere else it
exits nonzero without a result.  All work stays in this one process (the
ingest leaves are threads), which holds the chip.  The last line of a
passing run is one JSON object naming the device; earlier lines report
what was checked, the compile time and an informational tick rate.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@dataclasses.dataclass(frozen=True)
class Q1:
    """The q1 wordcount deployment; defaults are the smoke's real size."""
    k_virt: int = 65536
    vocab: int = 65536
    words: int = 6               # words (keys) per tweet
    tweets: int = 8192           # tweets per tick
    tick_ms: int = 250           # event time one tick spans
    n_ticks: int = 48
    n_sources: int = 4
    leaves: int = 2
    wa: int = 1000
    ws: int = 2000
    super_batch: int = 8
    n_active: int = 8
    n_max: int = 16


class ScaleUpOnce:
    """Scripted elasticity: the first decision asks for ``n_max``
    instances (a balanced f_mu over all of them), every later one keeps
    the layout."""

    def __init__(self, k_virt: int, n_max: int):
        self.k_virt, self.n_max = k_virt, n_max
        self.fired = False

    def observe_live(self, metrics):
        from repro.core.controller import (Reconfiguration, active_mask,
                                           balanced_fmu)
        if self.fired:
            return None
        self.fired = True
        return Reconfiguration(
            epoch=1, n_active=self.n_max,
            fmu=balanced_fmu(self.k_virt, self.n_max, self.n_max),
            active=active_mask(self.n_max, self.n_max))


def make_sink():
    from repro.io.sinks import CollectSink

    class TimedSink(CollectSink):
        """CollectSink that also keeps each dispatch's wall time and the
        output-buffer overflow counters."""

        def __init__(self):
            super().__init__()
            self.t_accept = []
            self.overflow = []

        def accept(self, tick_id, outs_pre, outs_post):
            self.t_accept.append(time.perf_counter())
            self.overflow += [outs_pre.overflow, outs_post.overflow]
            super().accept(tick_id, outs_pre, outs_post)

    return TimedSink()


def generate(q: Q1, seed: int):
    from repro.data import datagen
    return list(datagen.tweets(
        np.random.default_rng(seed), n_ticks=q.n_ticks, tick=q.tweets,
        words_per_tweet=q.words, vocab=q.vocab, k_virt=q.k_virt,
        rate_per_tick=q.tick_ms, n_sources=q.n_sources))


def reference_counts(batches, wa: int, ws: int):
    """Plain numpy sliding-window count: window ``l`` covers
    ``[l*wa, l*wa + ws)`` and counts, per key, the tuples whose key set
    holds it (a key repeated inside one tuple counts once).  Returns
    ``{(right boundary, key): count}``."""
    tau = np.concatenate([np.asarray(b.tau) for b in batches]).astype(np.int64)
    keys = np.concatenate([np.asarray(b.keys) for b in batches])
    keys = np.sort(keys, axis=1)
    first = np.ones(keys.shape, bool)
    first[:, 1:] = keys[:, 1:] != keys[:, :-1]
    use = (keys >= 0) & first
    l_lo = (tau - ws) // wa + 1
    l_hi = tau // wa
    wins, ks = [], []
    for d in range(-(-ws // wa)):
        l = l_lo + d
        m = use & (l <= l_hi)[:, None]
        wins.append(np.broadcast_to(l[:, None], keys.shape)[m])
        ks.append(keys[m].astype(np.int64))
    wins, ks = np.concatenate(wins), np.concatenate(ks)
    base = wins.min()
    code, count = np.unique((wins - base) * (1 << 32) + ks,
                            return_counts=True)
    right = (code >> 32) + base
    return {(int(r * wa + ws), int(k)): int(c)
            for r, k, c in zip(right, code & 0xFFFFFFFF, count)}


def run_q1(q: Q1, *, mesh_devices: int, seed: int):
    """Build the runtime from one RuntimeConfig, run the stream, return
    what the checks read."""
    from repro.api import RuntimeConfig, build_runtime
    from repro.io.sources import ReplaySource

    cfg = RuntimeConfig(
        op="count", wa=q.wa, ws=q.ws, k_virt=q.k_virt, out_cap=q.k_virt,
        extra_slots=2, n_max=q.n_max, n_active=q.n_active,
        stash_cap=max(q.tweets // 8, 128), mesh_devices=mesh_devices,
        n_sources=q.n_sources, ingest_hosts=q.leaves,
        ingest_worker="thread", leaf_cap=q.tweets, root_cap=q.tweets,
        out_pad=q.tweets, root_device=True, super_batch=q.super_batch)
    t0 = time.perf_counter()
    batches = generate(q, seed)
    t_gen = time.perf_counter() - t0
    sink = make_sink()
    rt = build_runtime(cfg, ReplaySource(batches, n_inputs=q.n_sources),
                       sink=sink, controller=ScaleUpOnce(q.k_virt, q.n_max))
    t0 = time.perf_counter()
    report = rt.run()
    t_run = time.perf_counter() - t0
    return dict(cfg=cfg, batches=batches, rt=rt, report=report, sink=sink,
                t_gen=t_gen, t_run=t_run, t_start=t0)


def check_outputs(q: Q1, res) -> None:
    """The run's answers against the numpy reference, window by window."""
    pipe, sink, rt = res["rt"].pipeline, res["sink"], res["rt"]
    sigma = pipe.sigma
    w_final = int(np.asarray(sigma.op_state.watermark))
    got = {}
    for tau, (key, count) in sink.results():
        k = (int(tau), round(key))
        check(k not in got, f"window {tau} emitted key {key} twice")
        got[k] = int(count)
    want = {k: c for k, c in reference_counts(res["batches"], q.wa,
                                              q.ws).items()
            if k[0] <= w_final}
    closed = sorted({r for r, _ in want})
    print(f"outputs: {len(got)} (window, key) counts over {len(closed)} "
          f"closed windows (right boundaries {closed[0]}..{closed[-1]} ms, "
          f"final watermark {w_final} ms)")
    check(len(closed) >= q.n_ticks * q.tick_ms // q.wa - 3,
          f"only {len(closed)} windows closed")
    missing = [k for k in want if k not in got]
    extra = [k for k in got if k not in want]
    wrong = [k for k in want if k in got and got[k] != want[k]]
    check(not missing and not extra and not wrong,
          f"output mismatch vs numpy reference: {len(missing)} missing, "
          f"{len(extra)} extra, {len(wrong)} wrong counts "
          f"(e.g. {(missing + extra + wrong)[:3]})")
    print(f"numpy reference: all {len(want)} (window, key) counts match")
    out_overflow = int(sum(int(np.sum(np.asarray(o))) for o in sink.overflow))
    stats = rt.tier.stats()
    counters = dict(output_buffers=out_overflow,
                    pipeline_stash=int(np.asarray(pipe.sg.overflow)),
                    window_ring=int(np.asarray(sigma.collisions)),
                    ingest=stats.total_overflow)
    print(f"overflow counters: {counters}")
    check(not any(counters.values()), f"overflow: {counters}")


def check_reconfig(q: Q1, res) -> None:
    report, pipe = res["report"], res["rt"].pipeline
    ticks = [t for t, _ in report.reconfig_trace]
    active = int(np.asarray(pipe.epoch.active).sum())
    print(f"reconfigurations: {len(ticks)} injected at ticks {ticks}, "
          f"{report.switches} switched; active instances "
          f"{q.n_active} -> {active} of {q.n_max}")
    check(ticks and 0 < ticks[0] < q.n_ticks, "no mid-stream reconfiguration")
    check(report.switches >= 1 and active == q.n_max,
          "the scale-up never switched")


def custom_calls(hlo: str):
    """Names of the TPU custom calls (Pallas kernels) in a compiled HLO."""
    return sorted({m.group(1) for m in re.finditer(
        r"%([A-Za-z_][\w]*?)(?:\.\d+)* = [^\n]*"
        r'custom_call_target="tpu_custom_call"', hlo)})


def rates(q: Q1, res) -> None:
    sink, report = res["sink"], res["report"]
    t = sink.t_accept
    steady = (q.super_batch * (len(t) - 2) / (t[-1] - t[1])
              if len(t) > 2 and t[-1] > t[1] else float("nan"))
    print(f"run: {report.ticks} dispatches of {q.super_batch} ticks, "
          f"{report.tuples} tuples in {res['t_run']} s (data generation "
          f"{res['t_gen']} s set-up, first dispatch after "
          f"{t[0] - res['t_start']} s)")
    print(f"steady ticks/s (informational, host clock): {steady}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run the stream on a 4-device mesh (and only that)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro import compile_cache
    compile_cache.enable()
    import jax
    from jax import monitoring
    from repro.kernels import dispatch

    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu",
          f"JAX found no TPU (platform {dev.platform!r}); refusing to run")
    backend = dispatch.default_backend()
    check(backend == "pallas", f"kernel backend is {backend!r}, not 'pallas'")
    n_mesh = 4 if args.four_chips else 1
    check(len(devices) >= n_mesh,
          f"{n_mesh} devices wanted, {len(devices)} visible")
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"kernel backend {backend}; mesh of {n_mesh}")

    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)

    q = Q1()
    print(f"deployment: q1 wordcount, {dataclasses.asdict(q)}")
    res = run_q1(q, mesh_devices=n_mesh, seed=args.seed)
    print(f"compile: {len(compiles)} programs, {sum(compiles)} s")
    rates(q, res)
    check_outputs(q, res)
    check_reconfig(q, res)

    pipe = res["rt"].pipeline
    if args.four_chips:
        acc = pipe.sigma.op_state.zeta["acc"]
        shard_devs = {s.device.id for s in acc.addressable_shards}
        coll = pipe.collective_bytes()
        print(f"sigma {acc.shape} key blocks on devices {sorted(shard_devs)};"
              f" collective bytes of the step: {coll}")
        check(len(shard_devs) == 4, "sigma is not on 4 distinct devices")
        check(sum(coll.values()) == 0, f"the step moves state: {coll}")
    else:
        kernels = custom_calls(pipe.persistent_hlo())
        print(f"kernels in the compiled persistent step: {kernels}")
        check({"segment_aggregate", "scalegate_merge"} <= set(kernels),
              f"kernels missing from the persistent step: {kernels}")
        tier = res["rt"].tier
        gates = [tier.root.state] + [h.gate.state
                                     for h in tier._handles.values()]
        platforms = sorted({d.platform for g in gates
                            for a in jax.tree.leaves(g) for d in a.devices()})
        print(f"ingest gates: root (fused={tier.root.device}, "
              f"{tier.root.rounds} rounds) and {len(gates) - 1} leaves, "
              f"on {platforms}")
        check(tier.root.rounds > 0 and len(gates) > 1
              and platforms == ["cpu"],
              "the ingest tier's gates are not on the host CPU")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        sys.exit(1)
