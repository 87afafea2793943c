"""Collection smoke: every test module and every library entry point must
import under the installed jax — the failure mode this guards against is a
jax API move (e.g. ``from jax import shard_map``) breaking collection of
half the suite without any test reporting it."""

import importlib
import pathlib
import sys

import pytest

TESTS_DIR = pathlib.Path(__file__).parent
TEST_MODULES = sorted(p.stem for p in TESTS_DIR.glob("test_*.py"))

LIB_MODULES = [
    "repro.launch.mesh",
    "repro.kernels.dispatch",
    "repro.kernels.scalegate_merge.ops",
    "repro.kernels.segment_aggregate.ops",
    "repro.kernels.window_join.ops",
    "repro.kernels.flash_attention.ops",
    "repro.kernels.linear_scan.ops",
    "repro.core.scalegate",
    "repro.core.aggregate",
    "repro.core.join",
    "repro.core.vsn",
    "repro.core.runtime",
    "repro.models.moe",
    "repro.launch.train",
]


@pytest.mark.parametrize("mod", TEST_MODULES)
def test_test_module_imports(mod):
    if str(TESTS_DIR) not in sys.path:
        sys.path.insert(0, str(TESTS_DIR))
    importlib.import_module(mod)


@pytest.mark.parametrize("mod", LIB_MODULES)
def test_library_module_imports(mod):
    importlib.import_module(mod)


def test_shard_map_call_sites_use_compat():
    """Every shard_map call site uses the installed jax's own
    ``jax.shard_map``: no module imports the experimental namespace or a
    version shim (the repo pins one jax; a second spelling is how the
    0.4.x/0.6 move once broke collection)."""
    src = pathlib.Path(__file__).parent.parent / "src"
    offenders, callers = [], []
    for py in src.rglob("*.py"):
        text = py.read_text()
        if ("from jax.experimental.shard_map" in text
                or "repro.compat" in text):
            offenders.append(str(py))
        if "shard_map(" in text:
            callers.append(py.name)
            if "jax.shard_map(" not in text:
                offenders.append(str(py))
    assert not offenders, offenders
    assert {"vsn.py", "moe.py"} <= set(callers), callers
