#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 perfbench/run.py --workload q1-wordcount.saturated \\
        --seed 7 --seconds 40 --trace 0

The cell (``BENCHMARK.json``) names a configuration and a traffic mix;
``spec`` finds their files.  One run:

1. set-up: make the stream from ``--seed`` (the configuration's query
   module), build the system through ``repro.api.build_runtime`` with the
   benchmark's source, sink and scripted controller, and start it.  Set-up
   ends at the first delivery of a dispatch during which nothing was
   compiled or loaded: every program the cell uses has then run.
   ``setup_s`` runs from process start to that moment, the first timed
   event;
2. the window: ``--seconds`` of the stream, offered by the traffic's loop
   module.  With ``--trace 1`` the profiler records the window and the
   program's spans are read over it;
3. the source stops, the system drains, and every (window, key) count it
   delivered is compared with the query's reference of the same events
   (``check.py``).

The last line of standard output is the result as one JSON object; the
last lines of standard error are the numbers compared, each with its
limit.  Without a TPU, with fewer chips than the cell asks for, or with a
kernel backend other than ``pallas`` it exits with code 2 and prints no
result.
"""

import time

T_START = time.perf_counter()

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
from typing import Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
for p in (os.path.join(REPO, "src"), REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

from perfbench import (check, delivery, generator, roofline,  # noqa: E402
                       spec, trace_reduce)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
STEP_MODULES = {True: r"jit__persistent_fn", False: r"jit_step"}


class NoDevice(RuntimeError):
    """The machine cannot run the cell: no result is printed."""


class RunFailed(RuntimeError):
    pass


def require_chips(chips: int):
    import jax
    from repro.kernels import dispatch
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    backend = dispatch.default_backend()
    if backend != "pallas":
        raise NoDevice(f"kernel backend is {backend!r}, not 'pallas'")
    return devs


class CompileLog:
    """Every program compiled, or loaded from the persistent cache, in the
    process: (time, event, program name); and the persistent cache's
    misses (programs it did not hold, so compiled here)."""

    def __init__(self):
        from jax import monitoring
        self.events: List = []
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on)
        monitoring.register_event_listener(self._on_event)

    def _on(self, event, secs, **kw):
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(), event,
                                str(kw.get("fun_name", "?"))))

    def _on_event(self, event, **kw):
        if event == CACHE_MISS_EVENT:
            self.cache_misses += 1

    def names(self, event: str, t0=-np.inf, t1=np.inf) -> List[str]:
        return [n for t, e, n in self.events if e == event and t0 <= t < t1]


class Runner(threading.Thread):
    """Runs ``Runtime.run()``; the main thread keeps the clock."""

    def __init__(self, rt):
        super().__init__(daemon=True, name="bench-runtime")
        self.rt, self.report, self.error = rt, None, None

    def run(self):
        try:
            self.report = self.rt.run()
        except BaseException as e:
            self.error = e


def span_totals(o) -> Dict:
    if o is None:
        return {}
    return {n[len("span."):]: (h.count, h.sum)
            for n, h in list(o.registry.histograms.items())
            if n.startswith("span.")}


def wait_for(cond, runner: Runner, timeout: float, what: str) -> None:
    deadline = time.perf_counter() + timeout
    while not cond():
        if runner.error is not None:
            raise RunFailed(f"the runtime failed during {what}: "
                            f"{runner.error!r}") from runner.error
        if not runner.is_alive():
            raise RunFailed(f"the runtime ended during {what}")
        if time.perf_counter() > deadline:
            raise RunFailed(f"timed out after {timeout} s during {what}")
        time.sleep(0.01)


def warm_up(sink, compiles: CompileLog, runner: Runner,
            timeout: float) -> float:
    """Set-up ends at the first delivery, after the first, of a dispatch
    during which no program was compiled or loaded.  Every program the
    cell uses has then run.  Returns that delivery's time."""
    k = 1
    while True:
        wait_for(lambda: len(sink.deliveries) > k, runner, timeout,
                 "set-up")
        d_prev, d = sink.deliveries[k - 1], sink.deliveries[k]
        if not compiles.names(COMPILE_EVENT, d_prev.t, d.t):
            return d.t
        k += 1


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, control: bool = False,
             setup_timeout: float = 1100.0,
             drain_timeout: float = 280.0) -> Dict:
    import jax
    from repro import obs as _obs
    from repro.api import RuntimeConfig, build_runtime

    cfg_d = {**cell.config, **cell.traffic.get("runtime", {})}
    cfg = RuntimeConfig.from_json(cfg_d)
    query = cell.module("queries", cfg_d["query"])
    compiles = CompileLog()
    window = generator.Window()
    loop = cell.module("loops", cell.traffic["loop"])
    source = generator.Source(query, loop, cell.traffic, cfg_d, seed, window)
    sink = delivery.DeliverySink(query.decode)
    ctl_spec = cell.traffic.get("controller")
    ctl = (None if ctl_spec is None else
           cell.module("controllers", ctl_spec["script"]).make(ctl_spec,
                                                               cfg_d))
    o = (_obs.install(_obs.ObsConfig(enabled=True, trace=True,
                                     flight=False)) if trace else None)
    rt = build_runtime(cfg, source, sink=sink, controller=ctl)
    delivery.tap_switches(rt.pipeline, sink)
    runner = Runner(rt)
    runner.start()
    trace_dir = None
    try:
        t0 = warm_up(sink, compiles, runner, setup_timeout)
        setup_s = t0 - t_start
        window.open(t0, seconds)
        spans0 = span_totals(o)
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
            # host events of the runtime and the benchmark's annotations,
            # without the Python tracer's per-call events and their cost
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        wait_for(lambda: time.perf_counter() >= window.t1, runner,
                 seconds + 60, "the window")
        t1 = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
        spans1 = span_totals(o)
        runner.join(drain_timeout)
        if runner.is_alive():
            window.stop.set()
            raise RunFailed(f"the drain took over {drain_timeout} s")
        if runner.error is not None:
            raise RunFailed(f"the runtime failed: {runner.error!r}"
                            ) from runner.error
    finally:
        window.stop.set()
        sink.close()
    devs = jax.devices()[:cell.chips]
    mem_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs)

    # -- what was delivered when ------------------------------------------
    ticks = source.emitted
    n_ticks = len(ticks)
    proc = delivery.processing_tick(ticks, cfg.n_sources)
    t_deliv = delivery.delivery_times(sink.deliveries, n_ticks)
    per_tick = np.bincount(np.concatenate(proc), minlength=n_ticks + 2)

    e2e = {}
    in_win = sorted((d for d in sink.deliveries if t0 < d.t <= t1),
                    key=lambda d: d.t)
    if len(in_win) >= 2:
        sel = (t_deliv > in_win[0].t) & (t_deliv <= in_win[-1].t)
        e2e["events_per_s"] = float(per_tick[sel].sum()
                                    / (in_win[-1].t - in_win[0].t))
    switched = [s for s in delivery.switches(sink.deliveries,
                                             getattr(ctl, "injected", []))
                if t0 < s[1] <= t1]
    if switched:
        e2e["reconfig_ms"] = float(np.mean([(d - i) * 1e3
                                            for i, d, _ in switched]))
    e2e["setup_s"] = float(setup_s)

    # -- correctness ------------------------------------------------------
    want = query.reference(ticks, cfg_d)
    got = tuple(np.concatenate([getattr(d, f) for d in sink.deliveries])
                for f in ("r", "key", "count"))
    must_close = query.must_close(
        int(delivery.watermarks(ticks, cfg.n_sources)[-1]), cfg_d)
    pipe = rt.pipeline
    overflow = (sum(d.overflow for d in sink.deliveries)
                + int(np.asarray(pipe.sg.overflow))
                + int(np.asarray(pipe.sigma.collisions))
                + rt.tier.stats().total_overflow)
    numbers = check.compare(got, want, must_close, overflow)
    ctl_numbers = None
    if control:
        ctl_numbers = check.compare(query.control(want), want, must_close,
                                    0)

    # -- per-layer --------------------------------------------------------
    metrics: Dict[str, Dict] = {}
    breakdown = None
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    if trace:
        tr = trace_reduce.reduce(trace_reduce.load(_find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        persistent = cfg.super_batch > 1
        ctx = {
            "spans": {k: (v[0] - spans0.get(k, (0, 0.0))[0],
                          v[1] - spans0.get(k, (0, 0.0))[1])
                      for k, v in spans1.items()},
            "trace": tr,
            "run": {"step_module": STEP_MODULES[persistent],
                    "ticks_per_run": cfg.super_batch if persistent else 1,
                    "shards": max(cfg.mesh_devices, 1),
                    "switch_ticks": [n for _, _, n in switched],
                    **query.work(source.pool, cfg_d)},
            "peaks": roofline.peaks(devs[0].device_kind),
        }
        for m in cell.per_layer:
            v = spec.reader(m["name"], cell.root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
        breakdown = {"device_ops": tr.top_ops(10),
                     "idle_gaps": tr.idle_gaps(10)}
    else:
        for m in cell.end_to_end:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    missing = [m["name"] for m in
               (cell.per_layer if trace else cell.end_to_end)
               if m["name"] not in metrics]

    result = {
        "correct": check.verdict(numbers),
        "attempted": int(numbers["compared"] + numbers["extra"]),
        "failed": int(numbers["missing"] + numbers["extra"]
                      + numbers["wrong"]),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["run"] = {
        "seed": seed, "events": int(sum(t.tau.size for t in ticks)),
        "ticks": n_ticks, "dispatches": len(sink.deliveries),
        "window_accept_s": [d.t_accept - t0 for d in in_win],
        "window_delivery_s": [d.t - t0 for d in in_win],
        "window_programs": compiles.names(COMPILE_EVENT, t0, t1),
        "setup_programs": len(compiles.names(COMPILE_EVENT, -np.inf, t0)),
        "cache_misses": compiles.cache_misses,
        "switch_ticks": [n for _, _, n in switched],
        "metrics_missing": missing,
        "closed_windows": numbers["closed_windows"]}
    if ctl_numbers is not None:
        result["control"] = {**{k: ctl_numbers[k] for k in check.LIMITS},
                             "correct": check.verdict(ctl_numbers)}
    result["checks"] = check.limits(numbers)
    return result


def _find_xplane(d: str) -> str:
    for root, _, files in os.walk(d):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(root, f)
    raise RunFailed(f"the profiler wrote no .xplane.pb under {d}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="also compare the query's control (the reference "
                         "in a lower precision) and print its numbers and "
                         "verdict; not part of a benchmark run")
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    from repro import compile_cache
    compile_cache.enable()
    import jax
    # every program of the cell goes to the persistent cache, however fast
    # it compiled, so only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        require_chips(cell.chips)
    except NoDevice as e:
        print(f"perfbench: {e}; no result", file=sys.stderr)
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      T_START, control=args.control)
    if "control" in result:
        print("control (bfloat16 reference in the program's place): "
              + json.dumps(result["control"]), file=sys.stderr)
    print(json.dumps(result["run"]), file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"{name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
