"""Spread of the pipeline step's device time per tick over the devices
that ran it: (max - min) / mean, in %.  Each device runs the step over
its own key block, so the spread shows how unevenly the keys' work falls
on the blocks; one device has no spread to show."""

from perfbench.readers import _step_runs


def read(ctx):
    per_dev = [sum(e - s for s, e, _ in runs) / len(runs)
               for _, runs in _step_runs(ctx) if runs]
    if len(per_dev) < 2:
        return None
    mean = sum(per_dev) / len(per_dev)
    return 100.0 * (max(per_dev) - min(per_dev)) / mean
