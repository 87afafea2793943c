"""Where JAX keeps its persistent compilation cache: one rule for every
entry point (``chip_smoke.py``, ``benchmarks/run.py``, the ``launch``
mains, the examples and ``tests/conftest.py``).

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing here
  sets a directory.
* Otherwise: the fixed ``<checkout>/.jax_cache`` (gitignored).  The path is
  part of each entry's key, so it never names a temporary directory, a
  process id or a time.
"""

from __future__ import annotations

import os
import pathlib
from typing import Optional

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> Optional[pathlib.Path]:
    """Turn the persistent cache on before the first compile; returns the
    directory set in code, or None when the environment names one."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return CACHE_DIR
