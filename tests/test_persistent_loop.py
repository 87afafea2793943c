"""Persistent compiled K-tick driver (ISSUE-6 tentpole): ``run_persistent``
scans K ticks inside ONE compiled ``lax.scan`` with donated ScaleGate and
sigma buffers.  The contracts under test:

  * tick-for-tick output parity with K sequential ``step`` calls — per-tick
    multisets, switch flags and instance loads, across consecutive
    super-batches (the donated carry must thread exactly);
  * a mid-scan reconfiguration (control tuples injected into the ctrl pad
    lanes *inside* the compiled program) lands on the exact tick the
    sequential oracle switches on, with identical outputs before and after;
  * donation safety: the pre-call state buffers are consumed by the scan
    (use-after-donate raises) while the pipeline object stays live;
  * the zero-host-transfer witness: the compiled persistent HLO contains no
    host transfer ops on the data lane;
  * the async runtime's ``super_batch=K`` grouping is output-identical to
    the per-tick synchronous loop;
  * the mesh pipeline's persistent scan matches its own sequential steps
    (1-device always; 8-device under the multi-device CI job).
"""

import numpy as np
import jax
import pytest

from repro.core.aggregate import count_aggregate
from repro.core.controller import Reconfiguration, active_mask, balanced_fmu
from repro.core.runtime import MeshPipeline, VSNPipeline
from repro.core.windows import WindowSpec
from repro.data import datagen
from repro.io.sinks import flatten_outputs
from repro.launch.mesh import host_transfer_ops, make_stream_mesh

K = 64
WS = WindowSpec(wa=50, ws=100, wt="multi")

N_DEV = len(jax.devices())
needs8 = pytest.mark.skipif(
    N_DEV < 8, reason="needs 8 devices (XLA_FLAGS="
                      "--xla_force_host_platform_device_count=8)")


def op():
    return count_aggregate(WS, k_virt=K, out_cap=512, extra_slots=2)


def stream(n_ticks=6, seed=0):
    rng = np.random.default_rng(seed)
    return list(datagen.tweets(rng, n_ticks=n_ticks, tick=16,
                               words_per_tweet=3, vocab=500, k_virt=K,
                               rate_per_tick=30))


def make_vsn():
    return VSNPipeline(op(), n_max=8, n_active=4, stash_cap=64)


def make_mesh(n_shards):
    return MeshPipeline(op(), make_stream_mesh(n_shards), stash_cap=64,
                        mode="fast-agg", agg_kind="count")


def reconfig():
    fmu = balanced_fmu(K, 3, 8)
    return Reconfiguration(epoch=1, n_active=3, fmu=fmu,
                           active=active_mask(3, 8))


def mesh_reconfig(n_shards):
    """A reconfiguration at mesh width: the epoch tables are per-shard, so
    the active mask must be n_shards wide (a 1-shard mesh gets the
    epoch-bump-only switch — tables unchanged, switch still observable)."""
    n_act = max(n_shards // 2, 1)
    return Reconfiguration(epoch=1, n_active=n_act,
                           fmu=balanced_fmu(K, n_act, n_shards),
                           active=active_mask(n_act, n_shards))


def sequential_ticks(pipe, batches, rc=None, rc_at=0):
    """The oracle: K individual steps; per-tick sorted output multiset +
    switch flag (+ inst load where the pipeline computes one)."""
    ticks = []
    for i, b in enumerate(batches):
        r = rc if (rc is not None and i == rc_at) else None
        if isinstance(pipe, VSNPipeline):
            o1, o2, sw, il = pipe.step_staged(b, reconfig=r)
            il = np.asarray(il)
        else:
            o1, o2, sw = pipe.step(b, reconfig=r)
            il = None
        ticks.append((sorted(flatten_outputs(o1) + flatten_outputs(o2)),
                      bool(np.asarray(sw)), il))
    return ticks


def persistent_ticks(out):
    k = int(np.asarray(out.switched).shape[0])
    ticks = []
    for i in range(k):
        o1 = jax.tree.map(lambda a: a[i], out.outs_pre)
        o2 = jax.tree.map(lambda a: a[i], out.outs_post)
        il = (None if out.inst_load is None
              else np.asarray(out.inst_load)[i])
        ticks.append((sorted(flatten_outputs(o1) + flatten_outputs(o2)),
                      bool(np.asarray(out.switched)[i]), il))
    return ticks


def assert_tickwise_equal(got, want):
    assert len(got) == len(want)
    for i, ((g_out, g_sw, g_il), (w_out, w_sw, w_il)) in enumerate(
            zip(got, want)):
        assert g_out == w_out, f"tick {i}: output multisets differ"
        assert g_sw == w_sw, f"tick {i}: switch flag differs"
        if g_il is not None and w_il is not None:
            assert (g_il == w_il).all(), f"tick {i}: inst loads differ"


# ----------------------------------------------------- steady state -------

def test_persistent_matches_sequential():
    batches = stream(n_ticks=6)
    want = sequential_ticks(make_vsn(), batches)
    out = make_vsn().run_persistent(batches)
    assert_tickwise_equal(persistent_ticks(out), want)


def test_consecutive_super_batches_thread_state():
    """Two back-to-back persistent scans over one pipeline must continue the
    (donated, updated-in-place) state exactly where the first left off."""
    batches = stream(n_ticks=8)
    want = sequential_ticks(make_vsn(), batches)
    pipe = make_vsn()
    got = (persistent_ticks(pipe.run_persistent(batches[:4]))
           + persistent_ticks(pipe.run_persistent(batches[4:])))
    assert_tickwise_equal(got, want)


# ------------------------------------------------- mid-scan reconfig ------

@pytest.mark.parametrize("rc_at", [0, 3])
def test_midscan_reconfig_matches_sequential(rc_at):
    batches = stream(n_ticks=6)
    rc = reconfig()
    want = sequential_ticks(make_vsn(), batches, rc=rc, rc_at=rc_at)
    out = make_vsn().run_persistent(batches, reconfig=rc, reconfig_at=rc_at)
    got = persistent_ticks(out)
    assert any(sw for _, sw, _ in got), "reconfig never switched"
    assert_tickwise_equal(got, want)


def test_midscan_reconfig_matches_static_outputs():
    """Zero state transfer means the switch is semantically invisible: the
    total output multiset with a mid-scan reconfig equals the run that
    never reconfigures."""
    batches = stream(n_ticks=6)
    static = make_vsn().run_persistent(batches)
    moved = make_vsn().run_persistent(batches, reconfig=reconfig(),
                                      reconfig_at=2)
    flat = lambda t: sorted(sum((o for o, _, _ in persistent_ticks(t)), []))
    assert flat(moved) == flat(static)


# ------------------------------------------------------- donation ---------

def test_donated_buffers_consumed_and_pipeline_live():
    pipe = make_vsn()
    batches = stream(n_ticks=4)
    pipe.step(batches[0])                       # realize sg at stream shape
    old_sg = jax.tree.leaves(pipe.sg)
    pipe.run_persistent(batches)
    donated = [a for a in old_sg
               if isinstance(a, jax.Array) and a.is_deleted()]
    if not donated:
        pytest.skip("backend does not honor buffer donation")
    with pytest.raises(RuntimeError):
        np.asarray(donated[0])
    # the pipeline itself is fine: its state was replaced, not freed
    pipe.run_persistent(stream(n_ticks=4, seed=1))


# ------------------------------------------- zero-host-transfer HLO -------

def test_persistent_hlo_has_no_host_transfers():
    pipe = make_vsn()
    pipe.run_persistent(stream(n_ticks=4))
    hlo = pipe.persistent_hlo()
    assert hlo.strip(), "no persistent executable was compiled"
    assert host_transfer_ops(hlo) == []


# ------------------------------------------------- async super-batch ------

def test_async_super_batch_matches_sync():
    from repro.core.async_runtime import AsyncStreamRuntime, run_sync
    from repro.io import SyntheticSource

    batches = stream(n_ticks=8)
    pipe_a = make_vsn()
    rt = AsyncStreamRuntime(pipe_a, SyntheticSource(iter(batches)),
                            queue_cap=4, super_batch=4)
    rt.run()
    _, sink_s = run_sync(make_vsn(), SyntheticSource(iter(batches)))
    assert rt.sink.results() == sink_s.results()


# ------------------------------------------------------------ mesh --------

@pytest.mark.parametrize("n_shards", [
    1, pytest.param(8, marks=needs8)])
def test_mesh_persistent_matches_sequential(n_shards):
    batches = stream(n_ticks=5)
    want = sequential_ticks(make_mesh(n_shards), batches)
    out = make_mesh(n_shards).run_persistent(batches)
    assert_tickwise_equal(persistent_ticks(out), want)


@pytest.mark.parametrize("n_shards", [
    1, pytest.param(8, marks=needs8)])
def test_mesh_persistent_midscan_reconfig(n_shards):
    batches = stream(n_ticks=5)
    rc = mesh_reconfig(n_shards)
    want = sequential_ticks(make_mesh(n_shards), batches, rc=rc, rc_at=2)
    out = make_mesh(n_shards).run_persistent(batches, reconfig=rc,
                                             reconfig_at=2)
    got = persistent_ticks(out)
    assert any(sw for _, sw, _ in got), "reconfig never switched"
    assert_tickwise_equal(got, want)


def test_mesh_persistent_hlo_has_no_host_transfers():
    pipe = make_mesh(1)
    pipe.run_persistent(stream(n_ticks=4))
    assert host_transfer_ops(pipe.persistent_hlo()) == []


@pytest.mark.parametrize("kind", ["vsn", "mesh"])
def test_stage_super_from_host_batches(kind):
    """The ingest tier hands over ticks committed to the host's CPU device;
    ``stage_super`` stacks them there (ctrl pad and no-op ticks included)
    and places the stack where the pipeline runs — the same stack, placed
    the same way, as from ticks on the default device."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.ingest import leaf as L

    pipe = make_vsn() if kind == "vsn" else make_mesh(1)
    batches = stream(n_ticks=3)
    k = 4                                   # one all-invalid no-op tick
    want = pipe.stage_super(batches, k)
    got = pipe.stage_super([L.to_host(b) for b in batches], k)
    place = (NamedSharding(pipe.mesh, P()) if kind == "mesh"
             else jax.sharding.SingleDeviceSharding(jax.devices()[0]))
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert g.shape[0] == k
        assert g.sharding.is_equivalent_to(place, g.ndim)
        assert w.sharding.is_equivalent_to(place, w.ndim)
    valid = np.asarray(got.valid)
    assert not valid[len(batches):].any()
    assert not valid[:, -pipe.op.n_inputs:].any()   # the ctrl pad lanes
    np.testing.assert_array_equal(
        valid[:len(batches), :batches[0].batch],
        np.stack([np.asarray(b.valid) for b in batches]))
