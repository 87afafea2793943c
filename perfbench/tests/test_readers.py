"""The readers of the program's work-and-waiting spans: each returns its
span's mean in ms, and None where the program has no such span."""

import time

import pytest

from perfbench import roofline, run, spec

SPANS = {
    "ingest.leaf_wait_ms": "leaf.fetch",
    "ingest.blocked_ms": "ingest.blocked",
    "runtime.starved_ms": "runtime.wait",
    "reconfig.pending_ms": "reconfig.pending",
    "reconfig.behind_ms": "reconfig.behind",
}


@pytest.mark.parametrize("metric", sorted(SPANS))
def test_reader_is_the_span_mean(metric):
    read = spec.reader(metric)
    spans = {"leaf.push": (4, 8.0), SPANS[metric]: (4, 1.5)}
    assert read({"spans": spans}) == pytest.approx(375.0)
    assert read({"spans": {"leaf.push": (4, 8.0)}}) is None
    assert read({"spans": {SPANS[metric]: (0, 0.0)}}) is None


def test_traced_cycle_run_reports_waiting(tiny_root, monkeypatch):
    monkeypatch.setattr(roofline, "peaks",
                        lambda kind: {"hbm_bytes_per_s": 1e11})
    res = run.run_cell(spec.load_cell("tiny.cycle", tiny_root), 2**33 + 5,
                       3.0, True, time.perf_counter())
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert not set(SPANS) & set(res["run"]["metrics_missing"])
    assert 0 < m["ingest.leaf_wait_ms"] <= m["ingest.leaf_push_ms"]
    assert 0 < m["reconfig.behind_ms"] <= m["reconfig.pending_ms"]
    assert m["ingest.blocked_ms"] >= 0 and m["runtime.starved_ms"] >= 0
