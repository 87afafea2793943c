"""Elastic LLM serving on the VSN slot pool: requests stream in, replicas
scale with zero KV-cache movement (vs the SN baseline that ships slots).

    PYTHONPATH=src:. python examples/elastic_serving.py
"""

import numpy as np
import jax

from repro import compile_cache
from repro.configs import get_config, reduced
from repro.models import transformer
from repro.serving.kv_pool import Request, ServingEngine


def main():
    compile_cache.enable()
    cfg = reduced(get_config("qwen3_14b"))
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, n_slots=6, max_seq=48, n_instances=4)
    eng.pool.reconfigure_vsn(2)          # start with 2 active replicas

    rng = np.random.default_rng(1)
    for uid in range(5):
        eng.submit(Request(uid=uid,
                           prompt=rng.integers(1, cfg.vocab, 4),
                           max_new=6, arrived=uid))
    finished = []
    tick = 0
    while len(finished) < 5 and tick < 40:
        finished += eng.tick()
        tick += 1
        if tick == 2:       # load spike: scale 2 -> 4 replicas
            sn = eng.pool.reconfigure_sn(4)     # what SN would ship now
            eng.pool.kv_bytes_moved = 0
            moved = eng.pool.reconfigure_vsn(4)
            print(f"[tick {tick}] scaled to 4 replicas: VSN moved {moved} B "
                  f"(tables), SN baseline would ship {sn} B of live KV")
        if tick == 6:       # drain: scale back down
            moved = eng.pool.reconfigure_vsn(2)
            print(f"[tick {tick}] scaled to 2 replicas, moved {moved} B")
    for r in finished:
        print(f"request {r.uid}: {len(r.out)} tokens {r.out}")
    print("elastic_serving OK")


if __name__ == "__main__":
    main()
