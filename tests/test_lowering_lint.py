"""Mosaic lowering lint as a tier-1 regression gate.

A rank-1 BlockSpec or a 1-D iota/``jnp.arange`` can never silently
reappear in any registered kernel: the structural lint runs over every
``dispatch.register_lint`` case on every tier-1 run, and the deliberately-
bad fixtures below pin that the lint actually *catches* the offenders the
Pallas interpreter hides.  ``test_chip_compile.py`` compiles the same
kernels for a described TPU v5e.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

# importing the ops modules registers kernels AND their lint cases
import repro.kernels.flash_attention.ops    # noqa: F401
import repro.kernels.linear_scan.ops        # noqa: F401
import repro.kernels.scalegate_merge.ops    # noqa: F401
import repro.kernels.segment_aggregate.ops  # noqa: F401
import repro.kernels.window_join.ops        # noqa: F401
from repro.kernels import dispatch, lowering

KERNELS = ("scalegate_merge", "scalegate_merge_stacked",
           "segment_aggregate", "window_join",
           "flash_attention", "linear_scan")


def test_every_registered_kernel_has_a_lint_case():
    """register_kernel and register_lint must stay paired: a new kernel
    without a lowering case would dodge the whole gate."""
    assert set(dispatch.registered()) == set(dispatch.lint_cases()) \
        == set(KERNELS)


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_passes_structural_lint(name):
    report = lowering.lint_case(dispatch.lint_cases()[name]())
    assert report.ok, "\n".join(report.errors)


def test_lint_registered_runs_all_kernels():
    reports = lowering.lint_registered()
    assert set(reports) == set(KERNELS)
    assert all(r.ok for r in reports.values())


# ------------------------------------------------- the lint catches bugs --

def _bad_case(bad_specs: bool, bad_iota: bool) -> lowering.KernelCase:
    """A minimal kernel reintroducing the exact offenders the 2-D rewrites
    removed: rank-1 BlockSpecs/out_shape and a 1-D ``jnp.arange``."""
    if bad_specs:
        specs = dict(
            grid=(1,),
            in_specs=[pl.BlockSpec((128,), lambda i: (0,))],
            out_specs=pl.BlockSpec((128,), lambda i: (0,)),
            out_shape=jax.ShapeDtypeStruct((128,), jnp.int32))
        arg = jnp.zeros((128,), jnp.int32)
    else:
        specs = dict(
            grid=(1,),
            in_specs=[pl.BlockSpec((1, 128), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((1, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((1, 128), jnp.int32))
        arg = jnp.zeros((1, 128), jnp.int32)

    def kern(x_ref, o_ref):
        x = x_ref[...]
        if bad_iota:
            x = x + jnp.arange(128, dtype=jnp.int32).reshape(x.shape)
        o_ref[...] = x

    def fn(x):
        return pl.pallas_call(kern, **specs, interpret=True)(x)

    return lowering.KernelCase("bad", fn=fn, args=(arg,), specs=specs)


def test_lint_rejects_rank1_blockspecs_and_out_shape():
    report = lowering.lint_case(_bad_case(bad_specs=True, bad_iota=False))
    assert not report.ok
    assert any("in_specs[0]" in e for e in report.errors)
    assert any("out_specs[0]" in e for e in report.errors)
    assert any("out_shape[0]" in e for e in report.errors)


def test_lint_rejects_1d_iota_inside_kernel_body():
    report = lowering.lint_case(_bad_case(bad_specs=False, bad_iota=True))
    assert not report.ok
    assert any("1-D iota" in e for e in report.errors)


def test_lint_ignores_1d_iota_outside_pallas_call():
    """The padding shims around the kernels may use jnp.arange freely —
    only the Mosaic-bound body is constrained."""
    good = _bad_case(bad_specs=False, bad_iota=False)

    def fn_with_host_arange(x):
        return good.fn(x + jnp.arange(128, dtype=jnp.int32).reshape(1, 128))

    case = lowering.KernelCase("host-arange", fn=fn_with_host_arange,
                               args=good.args, specs=good.specs)
    assert lowering.lint_case(case).ok


def test_lint_flags_missing_pallas_call():
    case = lowering.KernelCase(
        "no-call", fn=lambda x: x + 1,
        args=(jnp.zeros((1, 128), jnp.int32),),
        specs=dict(in_specs=[], out_specs=[], out_shape=[]))
    report = lowering.lint_case(case)
    assert not report.ok and any("no pallas_call" in e
                                 for e in report.errors)
