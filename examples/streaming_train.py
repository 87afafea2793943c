"""End-to-end driver: train a ~100M-param qwen3-family model on a streaming
token pipeline with async checkpointing and crash-resume.

Default runs a short smoke (--steps 30 on a ~10M config); the full run of
the deliverable is:

    PYTHONPATH=src:. python examples/streaming_train.py --full --steps 300
"""

import argparse
import dataclasses

from repro import compile_cache
from repro.configs import get_config, reduced
from repro.launch import train as T
from repro.models.config import ModelConfig


def config_100m() -> ModelConfig:
    base = get_config("qwen3_14b")
    return dataclasses.replace(
        base, n_layers=8, d_model=512, n_heads=8, n_kv_heads=4, d_head=64,
        d_ff=2048, vocab=32000, n_microbatches=1)   # ~100M params


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="~100M params (default: reduced smoke config)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--ckpt-dir", default="/tmp/streaming_train_ckpt")
    args = ap.parse_args()

    argv = ["--arch", "qwen3-14b", "--steps", str(args.steps),
            "--ckpt-dir", args.ckpt_dir, "--batch", "4", "--seq", "128"]
    if not args.full:
        argv.append("--reduced")
        T.main(argv)
    else:
        import repro.configs as RC
        cfg100 = config_100m()
        orig = RC.get_config
        RC.get_config = lambda name: cfg100 if name == "qwen3_14b" else orig(name)
        try:
            T.main(argv)
        finally:
            RC.get_config = orig
    print("streaming_train OK")


if __name__ == "__main__":
    main()
