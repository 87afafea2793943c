import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: 512 host
placeholder devices build the production meshes; every cell's step function
must lower and compile with the production shardings, and we extract
``memory_analysis`` / ``cost_analysis`` / the HLO collective schedule for
EXPERIMENTS.md §Dry-run and the §Roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod both] [--json out.json]
"""

import argparse
import functools
import json
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compile_cache
from repro.configs import ARCHS, canon, get_config
from repro.launch import specs as S
from repro.launch.mesh import make_production_mesh
from repro.models import model as M, sharding as shd, transformer
from repro.models.config import ModelConfig
from repro.optim import adamw

SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}

# long_500k needs sub-quadratic attention: run only for SSM/hybrid archs
# (DESIGN.md §Arch-applicability); full-attention archs record the skip.
LONG_OK_KINDS = ("rwkv", "hybrid")


def input_specs(cfg: ModelConfig, shape: dict):
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    b, s = shape["batch"], shape["seq"]
    if shape["kind"] == "train":
        return M.make_train_batch_shapes(cfg, b, s)
    if shape["kind"] == "prefill":
        if cfg.frontend == "token":
            return {"inputs": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        return {"inputs": jax.ShapeDtypeStruct((b, s, cfg.d_model),
                                               jnp.bfloat16)}
    # decode: one new token against a seq_len KV cache
    if cfg.frontend == "token":
        tok = jax.ShapeDtypeStruct((b,), jnp.int32)
    else:
        tok = jax.ShapeDtypeStruct((b, cfg.d_model), jnp.bfloat16)
    caches, states = jax.eval_shape(
        functools.partial(transformer.init_caches, cfg, b, s))
    return {"token": tok, "caches": caches, "states": states}


# collective accounting shared with the streaming mesh runtime
from repro.launch.mesh import (COLLECTIVE_RE, DTYPE_BYTES,  # noqa: F401
                               collective_bytes)


def build_step(cfg: ModelConfig, shape: dict, mesh, opt_cfg=None):
    """Returns (fn, example_args, in_shardings, out_shardings)."""
    rules = S.make_rules(cfg, tp=mesh.shape["model"])
    pspecs = S.param_specs(cfg)
    aparams = M.abstract_params(cfg)
    pspecs = S.fit_tree(pspecs, aparams, mesh)
    ns = lambda spec: jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), spec,
        is_leaf=lambda x: isinstance(x, P))
    with shd.use_rules(mesh, rules):
        dp = shd.resolve("batch")
    dp_axes = dp[0] if len(dp) and dp[0] is not None else None
    batch_spec = P(dp_axes)

    if shape["kind"] == "train":
        opt_cfg = opt_cfg or adamw.AdamWConfig()
        aopt = M.abstract_opt(aparams)
        dp_group = (("data",) if "pod" not in mesh.shape
                    else ("pod", "data"))
        dp_size = 1
        for a in dp_group:
            dp_size *= mesh.shape[a]
        zspec = S.opt_specs(aparams, pspecs, dp_size, dp_group)
        ospecs = adamw.OptState(mu=zspec, nu=zspec, step=P())
        batch = input_specs(cfg, shape)
        bspecs = S.fit_tree({k: P(dp_axes) for k in batch}, batch, mesh)

        def fn(params, opt_state, batch):
            with shd.use_rules(mesh, rules):
                return M.train_step(params, opt_state, batch, cfg=cfg,
                                    opt_cfg=opt_cfg)
        in_shard = (ns(pspecs), ns(ospecs), ns(bspecs))
        out_shard = (ns(pspecs), ns(ospecs), None)
        args = (aparams, aopt, batch)
    elif shape["kind"] == "prefill":
        batch = input_specs(cfg, shape)

        def fn(params, inputs):
            with shd.use_rules(mesh, rules):
                return M.prefill_step(params, inputs, cfg=cfg)
        ispec = S.fit_spec(batch_spec, batch["inputs"].shape, mesh)
        in_shard = (ns(pspecs), NamedSharding(mesh, ispec))
        out_shard = None
        args = (aparams, batch["inputs"])
    else:
        inp = input_specs(cfg, shape)
        cspec, sspec = S.cache_specs(cfg, rules)
        if inp["caches"] is not None:
            cspec = S.fit_tree(
                jax.tree.map(lambda _: cspec["k"], inp["caches"]) | {}
                if False else
                {"k": cspec["k"], "v": cspec["v"]}, inp["caches"], mesh)
        if inp["states"] is not None:
            if isinstance(sspec, P):
                sspec = S.fit_tree(
                    jax.tree.map(lambda _: sspec, inp["states"],
                                 is_leaf=lambda x: hasattr(x, "shape")),
                    inp["states"], mesh)
            else:
                sspec = S.fit_tree(sspec, inp["states"], mesh)

        def fn(params, caches, states, token):
            with shd.use_rules(mesh, rules):
                return M.decode_step(params, caches, states, token,
                                     jnp.int32(shape["seq"] - 1), cfg=cfg)
        tspec = S.fit_spec(batch_spec, inp["token"].shape, mesh)
        in_shard = (ns(pspecs),
                    ns(cspec) if inp["caches"] is not None else None,
                    ns(sspec) if inp["states"] is not None else None,
                    NamedSharding(mesh, tspec))
        out_shard = (None,
                     ns(cspec) if inp["caches"] is not None else None,
                     ns(sspec) if inp["states"] is not None else None)
        args = (aparams, inp["caches"], inp["states"], inp["token"])
    return fn, args, in_shard, out_shard


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             keep_hlo: bool = False, cfg: ModelConfig = None) -> dict:
    import dataclasses
    cfg = cfg or get_config(arch)
    shape = SHAPES[shape_name]
    if shape["kind"] == "train":
        # each microbatch must still split evenly over the dp group
        # (multi-pod dp=32: chameleon's 16 microbatches would leave half-
        # token shards); clamp so batch/microbatches % dp == 0.
        dp_total = 32 if multi_pod else 16
        max_mb = max(shape["batch"] // dp_total, 1)
        if cfg.n_microbatches > max_mb:
            cfg = dataclasses.replace(cfg, n_microbatches=max_mb)
    if shape_name == "long_500k" and cfg.kind not in LONG_OK_KINDS:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped (full attention)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    fn, args, in_shard, out_shard = build_step(cfg, shape, mesh)
    # donate the in-place state: params+opt (train), KV caches (decode) —
    # without donation every step holds two copies of the largest buffers.
    donate = {"train": (0, 1), "prefill": (), "decode": (1, 2)}[shape["kind"]]
    with mesh:
        jitted = jax.jit(fn, in_shardings=in_shard, out_shardings=out_shard,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    res = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "status": "ok",
        "compile_s": round(time.time() - t0, 1),
        "flops": float(cost.get("flops", 0.0)),
        "hlo_bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": coll,
        "argument_bytes_per_device": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes_per_device": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes_per_device": getattr(mem, "temp_size_in_bytes", None),
        "alias_bytes_per_device": getattr(mem, "alias_size_in_bytes", None),
        "peak_bytes_per_device": ((getattr(mem, "argument_size_in_bytes", 0) or 0)
                                  + (getattr(mem, "output_size_in_bytes", 0) or 0)
                                  + (getattr(mem, "temp_size_in_bytes", 0) or 0)
                                  - (getattr(mem, "alias_size_in_bytes", 0) or 0)),
    }
    if keep_hlo:
        res["hlo"] = hlo
    return res


def main(argv=None):
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)

    archs = ARCHS if (args.all or args.arch is None) else [canon(args.arch)]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    pods = {"single": [False], "multi": [True], "both": [False, True]}[
        args.multi_pod]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                try:
                    r = run_cell(arch, shape, mp)
                except Exception as e:  # a failing cell is a bug: surface it
                    r = {"arch": arch, "shape": shape, "multi_pod": mp,
                         "status": f"FAIL {type(e).__name__}: {e}"}
                results.append(r)
                tag = "2x16x16" if mp else "16x16"
                coll = r.get("collective_bytes", {})
                print(f"{arch:20s} {shape:12s} {tag:8s} {r['status']:28s} "
                      f"flops={r.get('flops', 0):.3e} "
                      f"peakGB={(r.get('peak_bytes_per_device') or 0)/2**30:.2f} "
                      f"coll={ {k: f'{v/2**20:.0f}MB' for k, v in coll.items()} }",
                      flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    bad = [r for r in results if r["status"].startswith("FAIL")]
    print(f"\n{len(results) - len(bad)}/{len(results)} cells passed")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
