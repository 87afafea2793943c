"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

Read with ``jax.profiler.ProfileData`` only.  A device is a plane named
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per operation
that ran on the device, and its line ``XLA Modules`` one event per run of a
compiled program.  Event times are in one clock across planes.

* busy: the union of a device's op intervals; the idle share of a window
  is 1 - busy / window.
* window: the profiler session, ``profile_stop_time - profile_start_time``
  of the plane ``Task Environment`` (else the extent of all events).
* op names: the TPU names an op event by its whole HLO instruction
  (``%segment_aggregate.23 = f32[...] custom-call(...)``); the reduction
  keeps the instruction's name (``segment_aggregate.23``).
* op time by name: the sum of an op's event durations; the top ops of
  the breakdown are ranked by self time (less the ops nested in one, as
  a ``while`` holds its body's).  A kernel is found
  by name: ``segment_aggregate`` matches ``segment_aggregate`` and
  ``segment_aggregate.3``, never ``segment_aggregate_x``.
* idle gaps: on the first device, the gaps between busy intervals and at
  the two ends of the window (event times count from the session's
  start), each labelled by the host event that overlaps it most (the
  benchmark's own ``bench.*`` annotations and the runtime's host events).
"""

from __future__ import annotations

import dataclasses
import gzip
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HLO_INSTRUCTION = re.compile(r"^%?([^\s=]+) = ")

Interval = Tuple[float, float, str]          # (start_ns, end_ns, name)


def load(path: str):
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def union_ns(iv: List[Interval]) -> List[Tuple[float, float]]:
    """Merge intervals into disjoint, sorted ones."""
    out: List[List[float]] = []
    for s, e, _ in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_ns(ops: List[Interval]) -> List[Tuple[str, float]]:
    """(name, self time) of each op: its duration less that of the ops
    nested in it (a ``while`` op holds the ops of its loop body)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    own = [e - s for s, e, _ in ops]
    stack: List[int] = []
    for i in order:
        s, e, _ = ops[i]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(ops[i][2], own[i]) for i in range(len(ops))]


def op_name(name: str) -> str:
    """``%name.3 = type op(...)`` -> ``name.3``; other names unchanged."""
    m = HLO_INSTRUCTION.match(name)
    return m.group(1) if m else name


def matches(name: str, kernel: str) -> bool:
    return re.fullmatch(re.escape(kernel) + r"(\.\d+)*", name) is not None


@dataclasses.dataclass
class Device:
    ops: List[Interval]
    modules: List[Interval]

    @property
    def busy_ns(self) -> float:
        return sum(e - s for s, e in union_ns(self.ops))

    def op_ns(self, kernel: str, inside: Optional[List[Interval]] = None
              ) -> Tuple[float, int]:
        """(total ns, calls) of ``kernel``, optionally only inside the
        given module runs."""
        evs = [(s, e) for s, e, n in self.ops if matches(n, kernel)]
        if inside is not None:
            spans = sorted((s, e) for s, e, _ in inside)
            starts = np.array([s for s, _ in spans])
            keep = []
            for s, e in evs:
                i = int(np.searchsorted(starts, s, side="right")) - 1
                if i >= 0 and e <= spans[i][1]:
                    keep.append((s, e))
            evs = keep
        return sum(e - s for s, e in evs), len(evs)

    def module_runs(self, pattern: str) -> List[Interval]:
        """Runs of a program whose name matches ``pattern`` (a regex on the
        module name without its ``(id)`` suffix)."""
        rx = re.compile(pattern)
        return [(s, e, n) for s, e, n in self.modules
                if rx.fullmatch(re.sub(r"\(\d+\)$", "", n))]


@dataclasses.dataclass
class Trace:
    devices: Dict[int, Device]
    host: List[Interval]
    window_ns: float

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices that ran anything."""
        used = [d for d in self.devices.values() if d.ops]
        return (sum(d.busy_ns for d in used) / len(used) / 1e9
                if used else 0.0)

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """Device ops that took most self time, summed over devices (s)."""
        tot: Dict[str, float] = {}
        for d in self.devices.values():
            for name, ns in self_ns(d.ops):
                tot[name] = tot.get(name, 0.0) + ns
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]

    def idle_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        """Longest idle gaps on the first device, by what the host did."""
        if not self.devices:
            return []
        dev = self.devices[min(self.devices)]
        busy = union_ns(dev.ops)
        if not busy:
            return []
        gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
        if 0 <= busy[0][0] and busy[-1][1] <= self.window_ns:
            gaps += [(0.0, busy[0][0]), (busy[-1][1], self.window_ns)]
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
        out = []
        for s, e in gaps:
            best, over = "host idle", 0.0
            for hs, he, name in self.host:
                ov = min(e, he) - max(s, hs)
                if ov > over and he - hs < 10 * (e - s) + 1e9:
                    best, over = name, ov
            out.append([best, (e - s) / 1e9])
        return out


def reduce(pd) -> Trace:
    devices: Dict[int, Device] = {}
    host: List[Interval] = []
    window = None
    lo, hi = np.inf, -np.inf
    for plane in pd.planes:
        if plane.name == "Task Environment":
            st = dict(plane.stats)
            if "profile_start_time" in st and "profile_stop_time" in st:
                window = float(st["profile_stop_time"]) - float(
                    st["profile_start_time"])
            continue
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = Device([], [])
            for line in plane.lines:
                target = {OPS_LINE: dev.ops,
                          MODULES_LINE: dev.modules}.get(line.name)
                if target is None:
                    continue
                for ev in line.events:
                    s, e = float(ev.start_ns), float(ev.end_ns)
                    target.append((s, e, op_name(ev.name)))
                    lo, hi = min(lo, s), max(hi, e)
            devices[int(m.group(1))] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        host.append((float(ev.start_ns), float(ev.end_ns),
                                     ev.name))
    if window is None or window <= 0:
        window = hi - lo if hi > lo else 0.0
    return Trace(devices, host, window)
