"""Kernel backend dispatch: one registry, three execution backends.

Every kernel in ``repro/kernels`` ships two realizations — the Pallas TPU
kernel and the pure-jnp ``ref.py`` oracle — and tier-1 must be correct and
*fast* on whatever backend the host actually has.  This registry picks the
realization at call time:

* ``"pallas"``            — the compiled Pallas kernel (TPU).
* ``"pallas-interpret"``  — the same kernel under the Pallas interpreter
                            (CPU-debuggable, slow; used for parity tests).
* ``"xla"``               — the jitted ``ref.py`` oracle, which XLA compiles
                            natively on any host.  This is the CPU fast path.

Resolution order for ``backend=None``:
  1. an explicit ``set_default_backend(...)`` (e.g. ``benchmarks/run.py
     --backend``),
  2. the ``REPRO_KERNEL_BACKEND`` environment variable,
  3. hardware: ``"pallas"`` iff a TPU is visible, else ``"xla"``.

Each ``kernels/*/ops.py`` registers its implementations at import time and
exposes a single ``<name>_op(..., backend=None)`` entry point; the core
callers (``core/scalegate.py``, ``core/aggregate.py``, ``core/join.py``)
and the benchmark harness all go through those entry points, so a backend
switch is one knob for the whole system.
"""

from __future__ import annotations

import functools
import os
from typing import Callable, Dict, Optional

import jax

BACKENDS = ("pallas", "pallas-interpret", "xla")
_ENV_VAR = "REPRO_KERNEL_BACKEND"

_REGISTRY: Dict[str, Dict[str, Callable]] = {}
_LINT_CASES: Dict[str, Callable] = {}
_DEFAULT_BACKEND: Optional[str] = None


class UnknownBackendError(ValueError):
    pass


def _check_backend(backend: str) -> str:
    if backend not in BACKENDS:
        raise UnknownBackendError(
            f"backend {backend!r} not in {BACKENDS}")
    return backend


@functools.lru_cache(maxsize=1)
def _has_tpu() -> bool:
    # no try/except: a TPU that fails to initialize must surface, not
    # quietly turn every kernel into its CPU oracle
    return jax.devices()[0].platform == "tpu"


def default_backend() -> str:
    """The backend used when callers pass ``backend=None``."""
    if _DEFAULT_BACKEND is not None:
        return _DEFAULT_BACKEND
    env = os.environ.get(_ENV_VAR)
    if env:
        return _check_backend(env)
    return "pallas" if _has_tpu() else "xla"


def set_default_backend(backend: Optional[str]) -> None:
    """Process-wide override (``None`` restores env/hardware resolution)."""
    global _DEFAULT_BACKEND
    _DEFAULT_BACKEND = None if backend is None else _check_backend(backend)


def register(name: str, backend: str, fn: Callable) -> None:
    _REGISTRY.setdefault(name, {})[_check_backend(backend)] = fn


def register_kernel(name: str, *, pallas: Callable, xla: Callable) -> None:
    """Register the standard triple for one kernel.

    ``pallas`` must accept ``interpret=`` (the Pallas-call escape hatch);
    ``xla`` is the jitted ref oracle.
    """
    register(name, "pallas", functools.partial(pallas, interpret=False))
    register(name, "pallas-interpret", functools.partial(pallas,
                                                         interpret=True))
    register(name, "xla", xla)


def resolve(backend: Optional[str] = None) -> str:
    """Resolve ``backend`` (or the default) to a concrete backend name.

    Entry points call this *outside* jit so the resolved name — not
    ``None`` — is the static argument; a later ``set_default_backend``
    therefore can never hit a stale jit cache.
    """
    return _check_backend(backend or default_backend())


def lookup(name: str, backend: Optional[str] = None) -> Callable:
    backend = resolve(backend)
    impls = _REGISTRY.get(name)
    if impls is None:
        raise KeyError(f"no kernel registered under {name!r}; "
                       f"known: {sorted(_REGISTRY)}")
    fn = impls.get(backend)
    if fn is None:
        raise KeyError(f"kernel {name!r} has no {backend!r} implementation; "
                       f"has: {sorted(impls)}")
    return fn


def registered() -> Dict[str, tuple]:
    """name -> tuple of available backends (introspection/tests)."""
    return {k: tuple(sorted(v)) for k, v in _REGISTRY.items()}


def register_lint(name: str, case_fn: Callable) -> None:
    """Register a kernel's Mosaic-lowering lint hook: a zero-arg factory
    returning a ``repro.kernels.lowering.KernelCase`` (factory, so the
    example arrays are only materialized when the lint actually runs).
    Every ``register_kernel`` caller must also register a lint case —
    ``tests/test_lowering_lint.py`` enforces the pairing."""
    _LINT_CASES[name] = case_fn


def lint_cases() -> Dict[str, Callable]:
    """name -> KernelCase factory for every lint-registered kernel."""
    return dict(_LINT_CASES)
