"""Benchmark harness: one module per paper table/figure (Q1-Q6) + kernels.

``python -m benchmarks.run [--backend xla|pallas|pallas-interpret]`` prints
``name,us_per_call,derived`` CSV rows (plus the §Roofline pointer — the
roofline table itself is produced by repro.launch.roofline against the
dry-run artifacts).  ``--backend`` sets the kernel dispatch default for the
whole run; unset, it resolves to ``xla`` on CPU hosts and ``pallas`` on TPU
(see ``repro.kernels.dispatch``).
"""

import argparse
import os
import sys
import traceback

# plain `python -m benchmarks.run` from a checkout: put src/ on the path
# (pytest gets this from pyproject's pythonpath; bare python does not)
_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)


def main(argv=None) -> None:
    from repro import compile_cache

    compile_cache.enable()
    import inspect

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", default=None,
                    choices=["xla", "pallas", "pallas-interpret"],
                    help="kernel dispatch backend (default: xla on CPU, "
                         "pallas on TPU)")
    ap.add_argument("--only", default=None,
                    help="comma-separated module suffixes to run "
                         "(e.g. kernels_bench,q1_wordcount)")
    ap.add_argument("--mesh", type=int, default=0,
                    help="run mesh variants over N devices where a bench "
                         "supports it (emulate with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--async", dest="async_", action="store_true",
                    help="run live-runtime async variants where a bench "
                         "supports them (q1/q3/q5: AsyncStreamRuntime "
                         "overlap gain, tick-latency quantiles, "
                         "detection→switch latency, async-vs-sync parity)")
    ap.add_argument("--ingest-hosts", type=int, default=0,
                    help="run multihost-ingest variants where a bench "
                         "supports them (q1/q3: N-leaf hierarchical "
                         "ScaleGate root-merge throughput scaling + "
                         "parity vs the single-gate oracle; combine with "
                         "--mesh for the mesh-pipeline parity gate)")
    ap.add_argument("--csv", default=None,
                    help="also write the result rows to this CSV file "
                         "(CI uploads it as a workflow artifact)")
    ap.add_argument("--bench-dir", default=None,
                    help="also write one BENCH_<module>.json "
                         "perf-trajectory artifact per q-module into this "
                         "directory (run config + that module's rows; "
                         "q1_wordcount -> BENCH_q1.json)")
    ap.add_argument("--obs-export", default=None, metavar="DIR",
                    help="install the observability layer (metrics + "
                         "flight recorder + tracing) for the whole bench "
                         "run and export metrics.json/metrics.prom/"
                         "flight.json into DIR at the end — informational "
                         "(instrumentation is live, so rows are not "
                         "comparable to an uninstrumented run)")
    args = ap.parse_args(argv)

    if args.obs_export:
        from repro import obs as _obs
        _obs.install(_obs.ObsConfig(enabled=True, trace=True,
                                    export_dir=args.obs_export))

    from repro.kernels import dispatch
    dispatch.set_default_backend(args.backend)
    print(f"# backend={dispatch.default_backend()}", flush=True)
    print("name,us_per_call,derived[,latency columns]")
    from benchmarks import common
    from benchmarks import (kernels_bench, q1_wordcount, q2_forward,
                            q3_scalejoin, q4_reconfig, q5_elastic_stress,
                            q6_nyse, q7_serving)
    mods = (q1_wordcount, q2_forward, q3_scalejoin, q4_reconfig,
            q5_elastic_stress, q6_nyse, q7_serving, kernels_bench)
    if args.only:
        keep = {s.strip() for s in args.only.split(",")}
        names = {m.__name__.split(".")[-1] for m in mods}
        unknown = keep - names
        if unknown:
            ap.error(f"--only: unknown module(s) {sorted(unknown)}; "
                     f"choose from {sorted(names)}")
        mods = tuple(m for m in mods if m.__name__.split(".")[-1] in keep)
    ok = True
    row_span = {}                      # module name -> its slice of ROWS
    for mod in mods:
        params = inspect.signature(mod.main).parameters
        kw = {}
        if "mesh" in params:
            kw["mesh"] = args.mesh
        if "async_" in params:
            kw["async_"] = args.async_
        if "ingest_hosts" in params:
            kw["ingest_hosts"] = args.ingest_hosts
        row0 = len(common.ROWS)
        try:
            mod.main(**kw)
        except Exception:
            ok = False
            common.emit(mod.__name__, 0.0, "FAIL (exception)")
            traceback.print_exc()
        row_span[mod.__name__.split(".")[-1]] = (row0, len(common.ROWS))
    bad = common.failed_rows()
    if args.csv:
        common.write_csv(args.csv)
    if args.bench_dir:
        import jax
        os.makedirs(args.bench_dir, exist_ok=True)
        config = dict(backend=dispatch.default_backend(), mesh=args.mesh,
                      async_=args.async_, ingest_hosts=args.ingest_hosts,
                      n_devices=len(jax.devices()))
        for name, (lo, hi) in row_span.items():
            if hi == lo:
                continue
            # q1_wordcount -> BENCH_q1.json; kernels_bench -> BENCH_kernels_bench.json
            short = name.split("_")[0] if name.startswith("q") else name
            path = os.path.join(args.bench_dir, f"BENCH_{short}.json")
            common.write_bench_json(path, name, common.ROWS[lo:hi], config)
            print(f"# wrote {path}", flush=True)
    if args.obs_export:
        from repro import obs as _obs
        o = _obs.get()
        if o is not None:
            paths = o.export(args.obs_export)
            print(f"# obs export: {sorted(paths.values())}", flush=True)
    if bad:
        print(f"# {len(bad)} FAIL row(s):", file=sys.stderr)
        for name, _, derived in bad:
            print(f"#   {name}: {derived}", file=sys.stderr)
    # the bench run gates: any FAIL row (not just exceptions) is nonzero
    if not ok or bad:
        sys.exit(1)


if __name__ == "__main__":
    main()
