"""Scripted elasticity (after ``chip_smoke.ScaleUpOnce``): every decision
asks for the next instance count of the traffic's ``n_active`` list, round
robin, with a balanced f_mu over that many instances.  The runtime
consults the controller once per dispatch, right before it, and injects
the reconfiguration into that dispatch's first tick; so every dispatch
injects one.  ``injected`` holds the host time of each decision."""

import time


class Cycle:
    def __init__(self, k_virt, n_max, n_active):
        self.k_virt, self.n_max = k_virt, n_max
        self.sequence = list(n_active)
        self.injected = []

    def observe_live(self, metrics):
        from repro.core.controller import (Reconfiguration, active_mask,
                                           balanced_fmu)
        n = self.sequence[len(self.injected) % len(self.sequence)]
        rc = Reconfiguration(
            epoch=len(self.injected) + 1, n_active=n,
            fmu=balanced_fmu(self.k_virt, n, self.n_max),
            active=active_mask(n, self.n_max))
        self.injected.append(time.perf_counter())
        return rc


def make(spec, cfg):
    return Cycle(cfg["k_virt"], cfg["n_max"], spec["n_active"])
