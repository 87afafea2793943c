"""A whole run of a fixture cell on the CPU (the chip check skipped), the
last line's schema, and the refusals."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from perfbench import roofline, run, spec

from conftest import REPO


@pytest.fixture
def cpu_peaks(monkeypatch):
    monkeypatch.setattr(roofline, "peaks",
                        lambda kind: {"hbm_bytes_per_s": 1e11})


def run_tiny(root, cell, trace=False, seconds=3.0):
    return run.run_cell(spec.load_cell(cell, root), 2**33 + 17, seconds,
                        trace, time.perf_counter(), control=True)


@pytest.mark.parametrize("traffic", ["closed", "cycle"])
def test_fixture_cell_runs_correct(tiny_root, traffic):
    res = run_tiny(tiny_root, f"tiny.{traffic}")
    assert res["correct"], res["checks"]
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["run"]["window_programs"] == []
    want = {"closed": "events_per_s", "cycle": "reconfig_ms"}[traffic]
    assert res["metrics"][want]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0
    assert res["control"]["wrong"] > 0
    assert res["control"]["correct"] is False
    json.loads(json.dumps(res))


def test_traced_run_reports_span_metrics(tiny_root, cpu_peaks):
    res = run_tiny(tiny_root, "tiny.closed", trace=True)
    assert res["correct"]
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    for m in ("ingest.leaf_push_ms", "ingest.root_merge_ms",
              "runtime.stage_ms"):
        assert res["metrics"][m]["value"] > 0


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "q1-wordcount.saturated", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_cpu_run_is_refused_without_a_result():
    p = _cli(REPO)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
