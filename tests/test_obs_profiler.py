"""repro.obs spans on the profiler's clock, and the waiting they split out.

The contracts under test:
  * with tracing on, every span of the stream path reaches the profiler
    trace's host plane under its exact name, with the id of the work it
    belongs to as an event stat (``round``, ``tick``, ``epoch``); a leaf's
    ``leaf.fetch`` lies inside its ``leaf.push`` of the same round; each
    span's trace events are as many as its ``span.*`` histogram counts;
  * with tracing off, ``repro.obs.span`` is the null singleton and builds
    no ``TraceAnnotation``;
  * a reconfiguration records ``reconfig.pending`` (decision to the drain
    that observes its switch, the interval ``RunReport.detect_to_switch_ms``
    measures) and ``reconfig.behind`` (decision to the drain of the
    dispatch handed over before its own).
"""

import os

import jax
import numpy as np
import pytest

from repro import api, obs
from repro.core.controller import (Reconfiguration, active_mask,
                                   balanced_fmu)
from repro.io.sources import RateSchedule, ReplaySource
from repro.obs import ObsConfig
from repro.obs.trace import _NULL_SPAN, Tracer
from repro.obs.registry import MetricsRegistry

K = 64
N_SRC = 4
N_MAX = 8

# the span names the trace must hold, and the id stat each carries
TRACED = {
    "leaf.push": "round", "leaf.fetch": "round", "root.merge": "round",
    "ingest.stage": "tick", "ingest.blocked": "tick",
    "runtime.wait": None, "runtime.dispatch": "tick",
    "runtime.drain": "tick",
}


@pytest.fixture
def obs_env():
    prev = obs.get()
    yield lambda **kw: obs.install(ObsConfig(**kw))
    obs.set_current(prev)


class Scripted:
    """Injects a reconfiguration at the given decisions, alternating
    between ``n_active`` counts."""

    def __init__(self, at=(1, 2), n_active=(N_MAX, 2)):
        self.at, self.n_active = at, n_active
        self.calls, self.epoch = 0, 0

    def observe_live(self, metrics):
        i, self.calls = self.calls, self.calls + 1
        if i not in self.at:
            return None
        n = self.n_active[self.epoch % len(self.n_active)]
        self.epoch += 1
        return Reconfiguration(epoch=self.epoch, n_active=n,
                               fmu=balanced_fmu(K, n, N_MAX),
                               active=active_mask(n, N_MAX))


def stream(n_ticks=8, seed=0):
    from repro.data import datagen
    rng = np.random.default_rng(seed)
    return list(datagen.tweets(rng, n_ticks=n_ticks, tick=16,
                               words_per_tweet=3, vocab=300, k_virt=K,
                               rate_per_tick=30, n_sources=N_SRC))


def run_stream(super_batch=2, controller=None, n_ticks=8):
    cfg = api.RuntimeConfig(
        op="count", wa=50, ws=100, wt="multi", k_virt=K, out_cap=512,
        n_max=N_MAX, n_active=2, stash_cap=64, n_sources=N_SRC,
        ingest_hosts=2, ingest_worker="thread", leaf_cap=32, root_cap=64,
        queue_cap=2, super_batch=super_batch)
    src = ReplaySource(stream(n_ticks), n_inputs=N_SRC,
                       schedule=RateSchedule(((n_ticks, 1000.0),)))
    return api.build_runtime(cfg, src, controller=controller).run()


def host_events(trace_dir):
    """{name: [(start_ns, end_ns, stats)]} of the host planes' events
    named in ``TRACED``."""
    from jax.profiler import ProfileData
    path = next(os.path.join(r, f) for r, _, fs in os.walk(trace_dir)
                for f in fs if f.endswith(".xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in TRACED:
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.end_ns, dict(ev.stats)))
    return out


# ------------------------------------------------------- profiler trace ----

@pytest.mark.parametrize("super_batch", [1, 2])
def test_spans_reach_profiler_trace_with_ids(tmp_path, obs_env, super_batch):
    o = obs_env(enabled=True, trace=True, flight=False)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        rep = run_stream(super_batch=super_batch, controller=Scripted())
    finally:
        jax.profiler.stop_trace()
    evs = host_events(str(tmp_path))
    hists = o.registry.histograms
    for name, key in TRACED.items():
        assert name in evs, f"span {name} missing from the trace"
        assert len(evs[name]) == hists[f"span.{name}"].count, name
        if key is not None:
            assert all(isinstance(st.get(key), int)
                       for _, _, st in evs[name]), (name, key)
    # a dispatch that carries a reconfiguration names its epoch
    epochs = sorted(st["epoch"] for _, _, st in evs["runtime.dispatch"]
                    if "epoch" in st)
    assert epochs == [rc.epoch for _, rc in rep.reconfig_trace] == [1, 2]
    # the dispatch's ids are shared along it: stage, put, dispatch, drain
    ticks = {st["tick"] for _, _, st in evs["runtime.dispatch"]}
    for name in ("ingest.stage", "ingest.blocked", "runtime.drain"):
        assert {st["tick"] for _, _, st in evs[name]} == ticks, name
    # every fetch lies inside a push of its own round
    pushes = evs["leaf.push"]
    for s, e, st in evs["leaf.fetch"]:
        assert any(ps <= s and e <= pe and pst["round"] == st["round"]
                   for ps, pe, pst in pushes), st
    # rounds seen by the leaves are the rounds the root merged
    assert ({st["round"] for _, _, st in evs["root.merge"]}
            <= {st["round"] for _, _, st in pushes})


def test_tracing_off_builds_no_annotation(monkeypatch, obs_env):
    built = []
    real = jax.profiler.TraceAnnotation

    def counting(*a, **kw):
        built.append(a)
        return real(*a, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    prev = obs.set_current(None)
    try:
        assert obs.span("leaf.push", round=3) is _NULL_SPAN
        run_stream(super_batch=2, controller=Scripted())
    finally:
        obs.set_current(prev)
    obs_env(enabled=True, trace=False)
    assert obs.span("runtime.dispatch", tick=1, epoch=2) is _NULL_SPAN
    tr = Tracer(MetricsRegistry(), enabled=False)
    assert tr.span("leaf.fetch", round=0) is _NULL_SPAN
    tr.interval("reconfig.pending", 0.0, 1.0, epoch=1)
    assert not tr.registry.histograms and not tr.finished
    assert built == []
    # the counter does see the annotations of a traced span
    obs_env(enabled=True, trace=True)
    with obs.span("leaf.push", round=3):
        pass
    assert built == [("leaf.push",)]


# ------------------------------------------------------ reconfiguration ----

def test_reconfig_pending_and_behind(obs_env):
    prev = obs.set_current(None)
    try:
        untraced = run_stream(controller=Scripted())
    finally:
        obs.set_current(prev)
    o = obs_env(enabled=True, trace=True, flight=False)
    rep = run_stream(controller=Scripted())
    assert len(rep.reconfig_trace) == 2
    hists = o.registry.histograms
    assert "bus.detect_to_switch_s" not in hists
    # one pending span per resolved detection, the same interval as the
    # report's detection-to-switch time
    pending = {r["ids"]["epoch"]: r["dur_s"] for r in o.tracer.finished
               if r["name"] == "reconfig.pending"}
    assert hists["span.reconfig.pending"].count == len(
        rep.detect_to_switch_ms) == len(pending) == 2
    assert sorted(v * 1e3 for v in pending.values()) == pytest.approx(
        sorted(rep.detect_to_switch_ms))
    behind = {r["ids"]["epoch"]: r["dur_s"] for r in o.tracer.finished
              if r["name"] == "reconfig.behind"}
    assert sorted(behind) == [1, 2]
    assert all(behind[e] <= pending[e] for e in behind)
    # tracing leaves the tick accounting as it was
    assert rep.detect_to_switch_ticks == untraced.detect_to_switch_ticks
    assert all(t >= 0 for t in rep.detect_to_switch_ticks)
