"""Shared arithmetic of the per-layer metric readers (``metrics/*.py``).

A reader gets ``ctx``:

* ``ctx["spans"]``  -- ``{span: (count, seconds)}`` of the program's
  ``repro.obs`` spans finished inside the window;
* ``ctx["trace"]``  -- the window's ``trace_reduce.Trace`` (None untraced);
* ``ctx["run"]``    -- what the harness knows of the run: ``step_module``
  (regex of the pipeline step program's name), ``ticks_per_run`` (ticks
  per run of it), ``shards`` (key blocks, one per device), the query's
  per-tick work (``tuples_per_tick``, ``hits_per_tick``,
  ``cells_per_tick``), and ``switch_ticks``: for each reconfiguration
  whose switch was delivered inside the window, the ticks from its
  injection to the tick that switched;
* ``ctx["peaks"]``  -- the device's row of ``peaks.json``.

A reader that finds nothing to read returns None, and the metric is left
out of the result line.
"""

from __future__ import annotations

from typing import Callable, Optional

from perfbench import roofline


def span_mean_ms(ctx, name: str) -> Optional[float]:
    count, total = ctx["spans"].get(name, (0, 0.0))
    return total / count * 1e3 if count else None


def _step_runs(ctx):
    tr = ctx["trace"]
    if tr is None:
        return []
    return [(dev, dev.module_runs(ctx["run"]["step_module"]))
            for dev in tr.devices.values()]


def tick_device_ms(ctx) -> Optional[float]:
    """Device time of the pipeline step program per tick, averaged over
    the devices that ran it."""
    per_dev = []
    for _, runs in _step_runs(ctx):
        if runs:
            ns = sum(e - s for s, e, _ in runs)
            per_dev.append(ns / 1e6 / (len(runs) * ctx["run"]["ticks_per_run"]))
    return sum(per_dev) / len(per_dev) if per_dev else None


def kernel_roofline(ctx, kernel: str,
                    bytes_per_tick: Callable[[dict], float],
                    per_block: bool) -> Optional[float]:
    """Least time over kernel time, in %, for the kernel's calls inside
    whole runs of the step program.  ``per_block``: each device does the
    work of its own key block only, so it owes ``1 / shards`` of a tick's
    least bytes; otherwise every device does the whole tick's."""
    run = ctx["run"]
    least, kernel_ns = 0.0, 0.0
    for dev, runs in _step_runs(ctx):
        ns, calls = dev.op_ns(kernel, inside=runs)
        if not calls:
            continue
        kernel_ns += ns
        least += (len(runs) * run["ticks_per_run"] * bytes_per_tick(run)
                  / (run["shards"] if per_block else 1))
    if kernel_ns <= 0:
        return None
    return roofline.share_pct(least, kernel_ns / 1e9, ctx["peaks"])
