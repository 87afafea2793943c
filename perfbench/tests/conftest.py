"""Shared fixtures of the harness tests: a tiny cell, defined only here,
that the harness finds by name in a checkout of its own."""

import json
import os
import shutil
import sys

# four virtual CPU devices for the mesh cases; set before JAX starts
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (os.path.join(REPO, "src"), REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {
    "name": "tiny", "query": "wordcount", "vocab": 512, "zipf_s": 1.3,
    "words_per_tweet": 6, "tweets_per_tick": 256, "tick_ms": 1437,
    "op": "count", "wa": 3000, "ws": 9000, "k_virt": 512, "out_cap": 512, "extra_slots": 2,
    "n_max": 4, "n_active": 4, "stash_cap": 64, "mesh_devices": 1,
    "n_sources": 4, "ingest_hosts": 2, "ingest_worker": "thread",
    "leaf_cap": 256, "root_cap": 256, "out_pad": 256, "root_device": True,
    "super_batch": 2, "queue_cap": 2, "chan_cap": 2,
}

TRAFFIC = {
    "closed": {"loop": "closed", "pool_ticks": 6},
    "cycle": {"loop": "closed", "pool_ticks": 6,
              "runtime": {"n_max": 8, "n_active": 4},
              "controller": {"script": "cycle", "n_active": [8, 4]}},
}


def make_root(tmp, config=None, mesh=1):
    """A checkout with only a BENCHMARK.json, the tiny configuration, the
    fixture's traffic mixes, and the real queries, loops, controllers and
    metric readers."""
    cfg = dict(TINY if config is None else config, mesh_devices=mesh)
    os.makedirs(os.path.join(tmp, "perfbench", "configs"))
    os.makedirs(os.path.join(tmp, "perfbench", "traffic"))
    for kind in ("queries", "loops", "controllers", "metrics"):
        shutil.copytree(os.path.join(BENCH, kind),
                        os.path.join(tmp, "perfbench", kind),
                        ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(tmp, "perfbench", "configs", "tiny.json"),
              "w") as f:
        json.dump(cfg, f)
    for name, t in TRAFFIC.items():
        with open(os.path.join(tmp, "perfbench", "traffic", name + ".json"),
                  "w") as f:
            json.dump(t, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    cells = [f"tiny.{t}" for t in TRAFFIC]
    bench = {
        "configs": [{"name": "tiny", "source": "fixture",
                     "file": "perfbench/configs/tiny.json", "reduced": [],
                     "why": "fixture"}],
        "workloads": [{"name": f"tiny.{t}", "config": "tiny",
                       "traffic": t, "chips": 1, "why": "fixture"}
                      for t in TRAFFIC],
        "end_to_end": [dict(m, workloads=cells) if "workloads" in m else m
                       for m in real["end_to_end"]],
        "per_layer": [dict(m, workloads=cells) for m in real["per_layer"]],
    }
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(str(tmp_path))
