import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Roofline analysis (§Roofline) via two-point unrolled decomposition.

XLA's ``cost_analysis()`` counts while-loop (scan) bodies ONCE and reports
*per-device* numbers (calibrated in EXPERIMENTS.md §Dry-run), so the
production program's scans (layers, microbatches, KV chunks) hide work.
We therefore lower each cell twice with everything unrolled —
``n_layers = 2p`` and ``4p`` (p = the gemma3 local:global period, else 1),
``scan_layers=False``, ``n_microbatches=1``, ``analysis_unroll=True`` —
and solve the linear model

    C(L) = C_fixed + L * C_layer          (per metric, per collective kind)

Total per-device cost = C_fixed + n_layers * C_layer.  The irreducibly
sequential rwkv/ssm time recurrences stay scanned; their (<2%) FLOPs are
added in closed form.  Peak memory comes from the *production* compile
(dryrun JSON), since peaks don't decompose linearly.

Terms (v5e: 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI, 6 links):
    T_comp = flops_dev / 197e12
    T_mem  = bytes_dev / 819e9
    T_coll = coll_bytes_dev / (6 * 50e9)
    roofline_fraction = (MODEL_FLOPS_dev / 197e12) / max(T_*)

Usage:
  PYTHONPATH=src python -m repro.launch.roofline --all --json roofline.json
  PYTHONPATH=src python -m repro.launch.roofline --cell qwen3_moe_30b_a3b:train_4k
"""

import argparse
import dataclasses
import json
import sys

from repro import compile_cache
from repro.configs import ARCHS, canon, get_config
from repro.models.config import ModelConfig

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9
LINKS = 6
CHIPS = 256
DP = 16                          # single-pod data-parallel degree

SHAPE_TOKENS = {"train_4k": 4096 * 256, "prefill_32k": 32768 * 32,
                "decode_32k": 128, "long_500k": 1}


def model_flops_per_device(cfg: ModelConfig, shape: str) -> float:
    n = cfg.active_param_count()
    toks = SHAPE_TOKENS[shape]
    mult = 6.0 if shape == "train_4k" else 2.0
    return mult * n * toks / CHIPS


def recurrence_flops_per_device(cfg: ModelConfig, shape: str) -> float:
    """Closed-form FLOPs of the scanned time recurrences (kept scanned)."""
    toks = SHAPE_TOKENS[shape]
    toks_dev = toks / DP if shape in ("train_4k", "prefill_32k") else toks / DP
    mult = 3.0 if shape == "train_4k" else 1.0   # fwd+bwd+remat vs fwd
    if cfg.kind == "rwkv":
        h = cfg.d_model // cfg.rwkv_head
        per_tok = 6 * h * cfg.rwkv_head * cfg.rwkv_head
    elif cfg.kind == "hybrid":
        per_tok = 6 * cfg.ssm_heads * cfg.ssm_state * cfg.head_dim
    else:
        return 0.0
    return mult * cfg.n_layers * per_tok * toks_dev


def _shape_dims(cfg: ModelConfig, shape: str):
    if shape == "train_4k":
        return 256 // DP, 4096, 4096, 3.0      # B_loc, Sq, Skv, passes
    if shape == "prefill_32k":
        return 32 // DP, 32768, 32768, 1.0
    if shape == "decode_32k":
        return 128 // DP, 1, 32768, 1.0
    return 1, 1, 524288, 1.0                   # long_500k


def attention_interior_bytes(cfg: ModelConfig, shape: str) -> float:
    """HBM bytes the XLA path spends on attention logits/probs that the
    Pallas flash kernel keeps in VMEM (s f32 w+r, p exp w+r: ~12 B/pair).
    Window layers cap the KV span at the window."""
    if not cfg.n_heads:
        return 0.0
    b, sq, skv, passes = _shape_dims(cfg, shape)
    heads_sharded = cfg.n_kv_heads % 16 == 0 and cfg.n_heads % 16 == 0
    h_dev = cfg.n_heads // 16 if heads_sharded else cfg.n_heads
    if cfg.window_pattern is not None:
        local, every = cfg.window_pattern
        span_local = min(local + 1024, skv)    # chunk granularity
        frac_g = 1.0 / every
        span = frac_g * skv + (1 - frac_g) * span_local
    else:
        span = skv
    pairs = b * sq * span * h_dev * cfg.n_layers
    return pairs * 12.0 * passes


def recurrence_interior_bytes(cfg: ModelConfig, shape: str) -> float:
    """HBM bytes of the per-step recurrent state the linear_scan kernel
    keeps in VMEM (state read+write per token: ~12 B/element)."""
    b, sq, _, passes = _shape_dims(cfg, shape)
    toks = b * sq
    if cfg.kind == "rwkv":
        h = cfg.d_model // cfg.rwkv_head
        elems = h * cfg.rwkv_head * cfg.rwkv_head
    elif cfg.kind == "hybrid":
        elems = cfg.ssm_heads * cfg.ssm_state * cfg.head_dim
    else:
        return 0.0
    return toks * elems * 12.0 * cfg.n_layers * passes


def measure_cell(arch: str, shape: str) -> dict:
    from repro.launch.dryrun import LONG_OK_KINDS, run_cell

    cfg = get_config(arch)
    if shape == "long_500k" and cfg.kind not in LONG_OK_KINDS:
        return {"arch": arch, "shape": shape,
                "status": "skipped (full attention)"}
    period = cfg.window_pattern[1] if cfg.window_pattern else 1
    l1, l2 = 2 * period, 4 * period
    points = {}
    for l in (l1, l2):
        cfg_a = dataclasses.replace(
            cfg, n_layers=l, scan_layers=False, n_microbatches=1,
            analysis_unroll=True)
        r = run_cell(arch, shape, multi_pod=False, cfg=cfg_a)
        if r["status"] != "ok":
            return {"arch": arch, "shape": shape,
                    "status": f"analysis-lower failed: {r['status']}"}
        coll = sum(r["collective_bytes"].values())
        points[l] = (r["flops"], r["hlo_bytes"], coll)

    def solve(i):
        c_layer = (points[l2][i] - points[l1][i]) / (l2 - l1)
        c_fixed = points[l1][i] - l1 * c_layer
        return c_fixed + cfg.n_layers * c_layer

    flops = solve(0) + recurrence_flops_per_device(cfg, shape)
    bytes_raw = solve(1)
    # kernelized memory: the Pallas flash/linear_scan kernels keep the
    # attention logits and recurrent state in VMEM — subtract their
    # closed-form HBM traffic from the unfused-XLA estimate.
    interior = (attention_interior_bytes(cfg, shape) +
                recurrence_interior_bytes(cfg, shape))
    bytes_kern = max(bytes_raw - interior, bytes_raw * 0.05)
    coll = max(solve(2), 0.0)
    return {"arch": arch, "shape": shape, "status": "ok",
            "flops_dev": flops, "bytes_dev": bytes_kern,
            "bytes_dev_raw": bytes_raw, "coll_dev": coll}


def min_bytes_per_device(cfg: ModelConfig, shape: str) -> float:
    """The memory floor: every chip must read its param shard once per step
    (TP=16: params replicated across the data axis) plus its KV/state
    slice — the MBU-style bound that governs decode."""
    tp = 16
    w = 2.0 * cfg.active_param_count() / tp
    b, sq, skv, _ = _shape_dims(cfg, shape)
    kv = 0.0
    if cfg.n_heads:
        kv = 2.0 * b * skv * cfg.kv_dim * 2 / tp     # kv_seq/model sharded
    if cfg.kind == "rwkv":
        kv = b * (cfg.d_model // cfg.rwkv_head) * cfg.rwkv_head ** 2 * 4
    if shape == "train_4k":
        w = w * 3 + 12.0 * cfg.active_param_count() / (tp * DP)  # grads+opt
    return w + kv


def analyse(rec: dict, peak_mem=None) -> dict:
    cfg = get_config(rec["arch"])
    t_comp = rec["flops_dev"] / PEAK_FLOPS
    t_mem = rec["bytes_dev"] / HBM_BW
    t_coll = rec["coll_dev"] / (LINKS * ICI_BW)
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops_per_device(cfg, rec["shape"])
    # the achievable floor is whichever physical resource binds first:
    # the MXU (compute) or the HBM read of weights+KV (decode regime).
    t_ideal = max(mf / PEAK_FLOPS,
                  min_bytes_per_device(cfg, rec["shape"]) / HBM_BW)
    return {
        **rec,
        "t_comp_s": t_comp, "t_mem_s": t_mem, "t_coll_s": t_coll,
        "t_mem_raw_s": rec.get("bytes_dev_raw", rec["bytes_dev"]) / HBM_BW,
        "dominant": dominant,
        "model_flops_dev": mf,
        "useful_ratio": mf / rec["flops_dev"] if rec["flops_dev"] else 0.0,
        "roofline_fraction": t_ideal / max(terms.values())
        if max(terms.values()) else 0.0,
        "peak_gb": peak_mem,
    }


def main(argv=None):
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--cell", default=None)
    ap.add_argument("--json", default=None)
    ap.add_argument("--peaks-from", default="dryrun_single.json")
    args = ap.parse_args(argv)

    peaks = {}
    if os.path.exists(args.peaks_from):
        with open(args.peaks_from) as f:
            for r in json.load(f):
                if r.get("status") == "ok" and not r.get("multi_pod"):
                    peaks[(r["arch"], r["shape"])] = \
                        (r.get("peak_bytes_per_device") or 0) / 2 ** 30

    from repro.launch.dryrun import SHAPES
    cells = ([tuple(args.cell.split(":"))] if args.cell else
             [(a, s) for a in ARCHS for s in SHAPES])

    rows = []
    hdr = (f"{'arch':20s} {'shape':12s} {'T_comp':>10s} {'T_mem':>10s} "
           f"{'T_coll':>10s} {'dom':>10s} {'useful':>7s} {'roofline':>9s} "
           f"{'peakGB':>7s}")
    print(hdr, flush=True)
    for arch, shape in cells:
        arch = canon(arch)
        rec = measure_cell(arch, shape)
        if rec["status"] != "ok":
            print(f"{arch:20s} {shape:12s} {rec['status']}", flush=True)
            rows.append(rec)
            continue
        w = analyse(rec, peaks.get((arch, shape)))
        rows.append(w)
        print(f"{arch:20s} {shape:12s} {w['t_comp_s']:10.3e} "
              f"{w['t_mem_s']:10.3e} {w['t_coll_s']:10.3e} "
              f"{w['dominant']:>10s} {w['useful_ratio']:7.1%} "
              f"{w['roofline_fraction']:9.1%} "
              f"{(w['peak_gb'] or 0):7.2f}", flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
