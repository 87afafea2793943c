"""Entry point for spawned worker processes that run on the host CPU.

A process that has touched JAX on a TPU machine holds the chip, and a
child that then initializes JAX on the TPU fails or hangs.  Ingest leaves
are CPU work by design, so a spawned leaf pins its own JAX to the CPU
before anything in it can reach the chip: ``run`` sets
``JAX_PLATFORMS=cpu`` and ``REPRO_KERNEL_BACKEND=xla`` in the child's
environment, then imports and calls its target.  (The spawn start-up may
already have imported JAX through the parent's ``__main__``; importing
JAX initializes no backend, and the config update below covers that
case.)

This module must stay free of JAX imports at top level: it is what the
child imports first.  Usage, from the parent::

    ctx.Process(target=host_worker.run,
                args=("repro.ingest.leaf:process_worker_main", cfg, ...))
"""

from __future__ import annotations

import importlib
import os


def run(target: str, *args):
    """Pin this process to the CPU, then call ``target`` ("module:function")
    with ``args``."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["REPRO_KERNEL_BACKEND"] = "xla"
    import jax
    jax.config.update("jax_platforms", "cpu")
    module, _, name = target.partition(":")
    return getattr(importlib.import_module(module), name)(*args)
