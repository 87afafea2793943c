"""Production training driver: mesh + shardings + checkpoint/resume +
streaming data + compute/comm overlap flags.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b \
        --steps 100 --ckpt-dir /tmp/ckpt [--reduced] [--grad-compress]

On the real cluster this runs once per host under the same jit program
(jax.distributed.initialize); here it drives whatever devices exist.
``--reduced`` shrinks the config to the smoke footprint so the full driver
path (resume, checkpoint cadence, metrics) is exercisable anywhere.
"""

import argparse
import os
import sys
import time

# Compute/communication overlap: let XLA's latency-hiding scheduler overlap
# collectives with compute (the standard large-scale flags).
os.environ.setdefault("XLA_FLAGS", "")
os.environ["XLA_FLAGS"] += (
    " --xla_tpu_enable_async_collective_fusion=true"
    " --xla_tpu_overlap_compute_collective_tc=true") \
    if "tpu" in os.environ.get("JAX_PLATFORMS", "") else ""

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.checkpoint import checkpoint as C
from repro.configs import canon, get_config, reduced
from repro.data import datagen
from repro.models import model as M, transformer
from repro.optim import adamw


def main(argv=None):
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args(argv)

    cfg = get_config(canon(args.arch))
    if args.reduced:
        cfg = reduced(cfg)
    opt_cfg = adamw.AdamWConfig(lr=args.lr, total_steps=args.steps,
                                warmup_steps=max(args.steps // 20, 1))

    key = jax.random.PRNGKey(0)
    params = transformer.init_params(key, cfg)
    opt = adamw.init_opt(params)
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"devices={len(jax.devices())}", flush=True)

    start_step, restored = 0, None
    latest = C.latest_step(args.ckpt_dir)
    if latest is not None:
        start_step, (params, opt) = latest, C.restore(
            args.ckpt_dir, latest, (params, opt))
        print(f"resumed from step {latest}", flush=True)

    step_fn = jax.jit(lambda p, o, b: M.train_step(
        p, o, b, cfg=cfg, opt_cfg=opt_cfg, chunk=min(1024, args.seq)))

    rng = np.random.default_rng(start_step)
    stream = datagen.token_batches(rng, vocab=cfg.vocab, batch=args.batch,
                                   seq=args.seq,
                                   n_batches=args.steps - start_step)
    t0 = time.time()
    for i, batch in enumerate(stream, start=start_step + 1):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        if cfg.frontend != "token":
            batch["inputs"] = jax.random.normal(
                jax.random.PRNGKey(i), (args.batch, args.seq, cfg.d_model),
                jnp.bfloat16)
        params, opt, m = step_fn(params, opt, batch)
        if i % 10 == 0 or i == start_step + 1:
            dt = (time.time() - t0)
            print(f"step {i} loss={float(m['loss']):.4f} "
                  f"gnorm={float(m['grad_norm']):.3f} "
                  f"tok/s={args.batch*args.seq*10/max(dt,1e-9):.0f}",
                  flush=True)
            t0 = time.time()
        if i % args.ckpt_every == 0:
            C.save(args.ckpt_dir, i, (params, opt))   # async
    C.wait(args.ckpt_dir)
    C.save(args.ckpt_dir, args.steps, (params, opt), async_=False)
    print("done", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
