"""Mosaic-lowering readiness: a structural lint that needs no TPU.

The Pallas interpreter (and the jnp ``xla`` oracles) will happily execute
kernel shapes the Mosaic TPU compiler rejects — rank-1 BlockSpecs and 1-D
iota/``jnp.arange`` are the canonical offenders (ROADMAP: "what the
interpreter hides").  This module makes that class of regression
*structurally impossible to miss* without TPU hardware in CI:

* ``lint_case`` checks a kernel's declared call structure (every
  ``BlockSpec`` block shape and every ``out_shape`` must be rank >= 2) and
  walks the traced kernel jaxpr inside each ``pallas_call`` equation for
  rank-1 ``iota`` — the primitive both ``jnp.arange`` and 1-D
  ``jax.lax.iota`` lower to.  Pure tracing: runs on any host, no TPU.

``tests/test_chip_compile.py`` goes further: it compiles every registered
kernel for a described (not attached) TPU v5e, which runs the full Mosaic
pipeline the chip runs.

Each ``kernels/*/ops.py`` registers a ``KernelCase`` factory with
``dispatch.register_lint``; the kernel modules expose their exact
``pallas_specs(...)`` so the linted structure can never drift from the
executed one.  ``tests/test_lowering_lint.py`` runs the lint over every
registered kernel as a tier-1 regression gate.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Sequence

import jax


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One lintable kernel: its public entry, representative inputs, and
    the spec structure the entry hands to ``pallas_call``."""
    name: str
    fn: Callable                  # full kernel entry; takes ``args`` arrays
    args: tuple                   # representative (small, padded) inputs
    specs: dict                   # grid / in_specs / out_specs / out_shape


@dataclasses.dataclass
class LintReport:
    kernel: str
    errors: List[str]

    @property
    def ok(self) -> bool:
        return not self.errors


def _as_list(x) -> list:
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _spec_errors(case: KernelCase) -> List[str]:
    """Rank-1 BlockSpecs / out_shapes are Mosaic-unlowerable: reject."""
    errs = []
    for field in ("in_specs", "out_specs"):
        for i, bs in enumerate(_as_list(case.specs.get(field, ()))):
            shape = tuple(bs.block_shape)
            if len(shape) < 2:
                errs.append(f"{field}[{i}]: rank-{len(shape)} BlockSpec "
                            f"{shape} (Mosaic needs rank >= 2)")
    for i, sds in enumerate(_as_list(case.specs.get("out_shape", ()))):
        if len(sds.shape) < 2:
            errs.append(f"out_shape[{i}]: rank-{len(sds.shape)} "
                        f"{tuple(sds.shape)} (Mosaic needs rank >= 2)")
    return errs


def _as_jaxpr(item):
    """Duck-typed Jaxpr/ClosedJaxpr detection — the classes moved between
    ``jax.core`` and ``jax.extend.core`` across the supported versions."""
    if hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
        return item.jaxpr                    # ClosedJaxpr
    if hasattr(item, "eqns") and hasattr(item, "invars"):
        return item                          # Jaxpr
    return None


def _sub_jaxprs(jaxpr) -> Sequence:
    """All jaxprs reachable from ``jaxpr``'s equation params (scan/cond/
    closed_call bodies ...), one level; callers recurse."""
    found = []
    for eqn in jaxpr.eqns:
        for val in eqn.params.values():
            for item in (val if isinstance(val, (list, tuple)) else [val]):
                sub = _as_jaxpr(item)
                if sub is not None:
                    found.append(sub)
    return found


def _iota_errors_in(jaxpr, where: str) -> List[str]:
    """Rank-1 iota anywhere under ``jaxpr`` (incl. scan/loop bodies)."""
    errs = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "iota":
            shape = tuple(eqn.params.get("shape", ()))
            if len(shape) < 2:
                errs.append(f"{where}: 1-D iota {shape} "
                            f"(use jax.lax.broadcasted_iota, rank >= 2)")
    for sub in _sub_jaxprs(jaxpr):
        errs.extend(_iota_errors_in(sub, where))
    return errs


def _trace_errors(case: KernelCase) -> List[str]:
    """Trace the public entry and lint the kernel jaxpr inside every
    pallas_call equation (the surrounding XLA-land padding shims may use
    1-D iota freely — only the Mosaic-bound body is constrained)."""
    try:
        traced = jax.make_jaxpr(case.fn)(*case.args)
    except Exception as e:                   # pragma: no cover - trace bug
        return [f"trace failed: {type(e).__name__}: {e}"]
    errs = []
    n_calls = 0

    def walk(jaxpr):
        nonlocal n_calls
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                n_calls += 1
                inner = _as_jaxpr(eqn.params.get("jaxpr"))
                if inner is not None:
                    errs.extend(_iota_errors_in(
                        inner, f"{case.name} kernel body"))
        for sub in _sub_jaxprs(jaxpr):
            walk(sub)

    walk(traced.jaxpr)
    if n_calls == 0:
        errs.append("no pallas_call found in trace (lint case is broken)")
    return errs


def lint_case(case: KernelCase) -> LintReport:
    """The structural Mosaic lint: spec ranks + kernel-body iota ranks."""
    return LintReport(case.name, _spec_errors(case) + _trace_errors(case))


def lint_registered() -> Dict[str, LintReport]:
    """Lint every kernel registered via ``dispatch.register_lint``."""
    from repro.kernels import dispatch

    reports = {}
    for name, case_fn in sorted(dispatch.lint_cases().items()):
        reports[name] = lint_case(case_fn())
    return reports
