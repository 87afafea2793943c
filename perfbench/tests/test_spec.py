"""Discovery by name, and the shape of BENCHMARK.json."""

import json
import os
import re

import pytest

from perfbench import spec

from conftest import REPO


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_fixture_cell_is_found_by_name(tiny_root):
    cell = spec.load_cell("tiny.cycle", tiny_root)
    assert cell.config["k_virt"] == 512
    assert cell.traffic["controller"]["script"] == "cycle"
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    read = spec.reader("device.idle_share", tiny_root)
    assert read({"trace": None}) is None


def test_modules_are_found_by_name_in_the_checkout(tiny_root):
    """A controller script that exists only in the fixture's checkout is
    found by the name its traffic mix gives."""
    import json
    import os
    base = os.path.join(tiny_root, "perfbench")
    with open(os.path.join(base, "controllers", "fixture_hold.py"),
              "w") as f:
        f.write("def make(spec, cfg):\n    return spec['script']\n")
    with open(os.path.join(base, "traffic", "cycle.json")) as f:
        t = json.load(f)
    t["controller"] = {"script": "fixture_hold"}
    with open(os.path.join(base, "traffic", "cycle.json"), "w") as f:
        json.dump(t, f)
    cell = spec.load_cell("tiny.cycle", tiny_root)
    ctl = cell.traffic["controller"]
    assert cell.module("controllers", ctl["script"]).make(
        ctl, cell.config) == "fixture_hold"
    assert callable(cell.module("queries", cell.config["query"]).reference)
    assert callable(cell.module("loops", cell.traffic["loop"]).ticks)


def test_unknown_names_are_errors(tiny_root):
    with pytest.raises(spec.SpecError):
        spec.load_cell("tiny.nothing", tiny_root)
    with pytest.raises(spec.SpecError):
        spec.reader("no.such.metric", tiny_root)
    with pytest.raises(spec.SpecError):
        spec.load_module("queries", "no_such_query", tiny_root)


def test_every_cell_of_the_benchmark_resolves():
    b = bench()
    for w in b["workloads"]:
        cell = spec.load_cell(w["name"])
        cell.module("queries", cell.config["query"])
        cell.module("loops", cell.traffic["loop"])
        if "controller" in cell.traffic:
            cell.module("controllers", cell.traffic["controller"]["script"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert callable(spec.reader(m["name"]))
            assert m["moves"] in names, (w["name"], m["name"])


def test_names_and_units_follow_the_contract():
    b = bench()
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    for m in b["end_to_end"] + b["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
    for x in b["workloads"] + b["configs"]:
        assert name.match(x["name"])
    for m in b["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
