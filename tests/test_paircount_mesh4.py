"""Q1 paircount (12 pair keys a tweet) with its state sharded over four
devices, through ``build_runtime``: exact counts, exactly once, against a
plain reference and against the one-device pipeline, with no bounded
buffer overflowing.

The four devices are virtual CPU devices, which exist only when
``XLA_FLAGS`` asks for them before JAX starts, so the runs go to a child
process (this file, run as a script).
"""

import json
import os
import subprocess
import sys

import numpy as np

from windowed_count import window_counts

K_VIRT, WA, WS = 4096, 100, 300
N_TICKS, TWEETS, SPAN = 20, 48, 70


def _stream():
    from repro.data import datagen
    return list(datagen.tweets(
        np.random.default_rng(2**33 + 15), n_ticks=N_TICKS, tick=TWEETS,
        words_per_tweet=6, vocab=120, k_virt=K_VIRT, mode="paircount",
        pair_dist=3, rate_per_tick=SPAN))


class _Sink:
    """(boundary, key, count) rows and the overflow of every output."""

    def __init__(self):
        self.rows, self.overflow = [], 0

    def accept(self, tick_id, *outs):
        for o in outs:
            valid = np.asarray(o.valid)
            pay = np.asarray(o.payload)[valid]
            self.rows += zip(np.asarray(o.tau)[valid].tolist(),
                             np.rint(pay[:, 0]).astype(int).tolist(),
                             pay[:, 1].tolist())
            self.overflow += int(np.sum(np.asarray(o.overflow)))


def _run(mesh_devices):
    from repro.api import RuntimeConfig, build_runtime
    from repro.io.sources import ReplaySource
    cfg = RuntimeConfig(op="count", wa=WA, ws=WS, k_virt=K_VIRT,
                        out_cap=K_VIRT, extra_slots=2, n_max=8, n_active=8,
                        stash_cap=64, mesh_devices=mesh_devices,
                        super_batch=4, queue_cap=2)
    sink = _Sink()
    rt = build_runtime(cfg, ReplaySource(_stream()), sink=sink)
    rt.run()
    pipe = rt.pipeline
    over = (sink.overflow + int(np.asarray(pipe.sg.overflow))
            + int(np.asarray(pipe.sigma.collisions)))
    return {"rows": sorted(sink.rows), "overflow": over,
            "shards": pipe.n_shards}


def _child():
    import jax
    assert len(jax.devices()) == 4, jax.devices()
    print(json.dumps({"mesh4": _run(4), "mesh1": _run(1)}))


def test_paircount_on_four_shards_is_exact():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    here = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(here, os.pardir, "src"), here,
         env.get("PYTHONPATH", "")])
    p = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    mesh4, mesh1 = out["mesh4"], out["mesh1"]
    assert (mesh4["shards"], mesh1["shards"]) == (4, 1)
    assert mesh4["overflow"] == mesh1["overflow"] == 0

    ticks = _stream()
    assert ticks[0].kmax == 12
    want = window_counts(np.concatenate([np.asarray(t.tau) for t in ticks]),
                         np.concatenate([np.asarray(t.keys) for t in ticks]),
                         WA, WS)
    got = {(int(r), int(k)): c for r, k, c in mesh4["rows"]}
    assert len(got) == len(mesh4["rows"])           # each (window, key) once
    closed = max(r for r, _ in got)
    want = {rk: c for rk, c in want.items() if rk[0] <= closed}
    assert len({r for r, _ in want}) >= 10          # windows compared
    assert got == want
    assert max(want.values()) > 1                   # repeated pairs count
    assert [tuple(r) for r in mesh1["rows"]] == [tuple(r)
                                                for r in mesh4["rows"]]


def test_keys_beyond_float32_integers_arrive_exactly():
    """Output payloads are float32: over a key space beyond 2^24 the count
    aggregate emits each key in two exact parts, and below it the key
    alone, as before."""
    import jax.numpy as jnp

    from repro.core.aggregate import count_aggregate
    from repro.core.windows import WindowSpec

    window = WindowSpec(wa=WA, ws=WS)
    keys = np.asarray([0, 255, 2**24 + 1, 2**27 + 77, 2**29 - 1], np.int32)
    counts = np.arange(1, 6, dtype=np.float32)[:, None]
    op = count_aggregate(window, k_virt=2**29)
    pay, valid = (np.asarray(a) for a in op.f_o(
        {"acc": jnp.asarray(counts)}, 0, jnp.asarray(keys)))
    assert op.payload_out == pay.shape[1] == 3 and valid.all()
    np.testing.assert_array_equal(
        np.rint(pay[:, 0]).astype(np.int64)
        + np.rint(pay[:, 2]).astype(np.int64), keys)
    np.testing.assert_array_equal(pay[:, 1], counts[:, 0])
    small = count_aggregate(window, k_virt=2**16)
    pay, _ = small.f_o({"acc": jnp.asarray(counts[:2])}, 0,
                       jnp.asarray(keys[:2]))
    assert small.payload_out == 2
    np.testing.assert_array_equal(np.asarray(pay)[:, 0], keys[:2])


if __name__ == "__main__":
    _child()
