"""The check must catch a broken timed path.

Each case drives a whole run of a fixture cell on the CPU (the chip check
skipped) with the program broken underneath, and sees ``correct`` come out
false: a step that returns its state unchanged, half of each tick left
out, the exchange between chips left out (four virtual devices), and one
answer altered where it is produced.  The bfloat16 control is checked in
``test_run.py`` (its ``wrong`` count) and on the chip.
"""

import dataclasses
import time

import jax.numpy as jnp
import pytest

from perfbench import run, spec
from repro.core import aggregate, vsn

from conftest import make_root


def run_fixture(root, cell="tiny.closed"):
    return run.run_cell(spec.load_cell(cell, root), 2**33 + 29, 3.0, False,
                        time.perf_counter())


def state_unchanged(monkeypatch):
    real = vsn.pipeline_tick

    def tick(sg, epoch, sigma, *a, **kw):
        out = real(sg, epoch, sigma, *a, **kw)
        return (sg, epoch, sigma) + tuple(out[3:])
    monkeypatch.setattr(vsn, "pipeline_tick", tick)


def half_batch(monkeypatch):
    real = aggregate.tick_fast

    def tick(op, kind, st, ready, resp, **kw):
        keep = jnp.arange(ready.batch) % 2 == 0
        return real(op, kind, st,
                    dataclasses.replace(ready, valid=ready.valid & keep),
                    resp, **kw)
    monkeypatch.setattr(aggregate, "tick_fast", tick)


def no_exchange(monkeypatch):
    """Only the first chip sees the replicated ready batch."""
    real = vsn.fast_agg_local_tick

    def make(op, kind, backend=None):
        inner = real(op, kind, backend)

        def local(lo, rows):
            fn = inner(lo, rows)

            def tick(state, ready):
                return fn(state, dataclasses.replace(
                    ready, valid=ready.valid & (lo == 0)))
            return tick
        return local
    monkeypatch.setattr(vsn, "fast_agg_local_tick", make)


def answer_altered(monkeypatch):
    real = aggregate.tick_fast

    def tick(*a, **kw):
        st, outs = real(*a, **kw)
        first = jnp.cumsum(outs.valid.astype(jnp.int32)) == 1
        bump = (first & outs.valid).astype(outs.payload.dtype)
        return st, dataclasses.replace(
            outs, payload=outs.payload.at[:, -1].add(bump))
    monkeypatch.setattr(aggregate, "tick_fast", tick)


@pytest.mark.parametrize("fault,mesh", [
    (state_unchanged, 1), (half_batch, 1), (no_exchange, 4),
    (answer_altered, 1)])
def test_fault_is_not_correct(tmp_path, monkeypatch, fault, mesh):
    root = make_root(str(tmp_path), mesh=mesh)
    sound = run_fixture(root)
    assert sound["correct"], sound["checks"]
    fault(monkeypatch)
    res = run_fixture(root)
    assert not res["correct"]
    assert res["failed"] > 0 or res["checks"]["overflow"]["value"] > 0
