"""Shared test helpers.  NOTE: no XLA_FLAGS here — smoke tests and benches
must see the single real CPU device; only launch/dryrun.py forces 512."""

try:                                   # optional test extra (pyproject.toml)
    import hypothesis                  # noqa: F401
except ImportError:                    # deterministic minimal stand-in
    from _hypothesis_fallback import install as _install_hypothesis
    _install_hypothesis()

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import compile_cache
from repro.core import tuples as T

# Persistent XLA compilation cache (repro.compile_cache): the suite is
# compile-dominated on CPU (hundreds of distinct jit shapes), and the cache
# cuts repeat tier-1 runs to a fraction of the cold time.
compile_cache.enable()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


def make_stream_batch(taus, keys=None, payload=None, source=None, kmax=1):
    taus = np.asarray(taus, np.int32)
    n = len(taus)
    if payload is None:
        payload = np.zeros((n, 1), np.float32)
    if keys is not None:
        keys = np.asarray(keys, np.int32)
        if keys.ndim == 1:
            keys = keys[:, None]
    return T.make_batch(jnp.asarray(taus), jnp.asarray(payload),
                        keys=None if keys is None else jnp.asarray(keys),
                        source=None if source is None else jnp.asarray(source),
                        kmax=kmax)


def collect_outputs(outs, n_instances=None):
    """Flatten (possibly per-instance stacked) Outputs to a sorted list of
    (tau, payload tuple) — the repo-wide parity currency
    (repro.io.sinks.flatten_outputs)."""
    from repro.io.sinks import flatten_outputs
    return sorted(flatten_outputs(outs))
