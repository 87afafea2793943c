"""Leaf ScaleGate: one ingest worker's merge over its owned sources.

A leaf is the paper's per-host ScaleGate (§6 hierarchical TB): it merges
the timestamp-sorted streams of its *disjoint* source subset into a ready
stream that is itself timestamp-sorted — so the leaf outputs compose as
sources of the root merge one level up.  The leaf is a thin, host-driven
wrapper around the same ``scalegate.push`` the pipelines use, run where
the gate lives (``host_device``: the host's CPU, off the chip's queue):

* per round it pushes its routed slice (chunked to a fixed lane width so
  jit shapes stay static) and emits a ``LeafOut`` — the *compacted* ready
  tuples plus the leaf's reported watermark ``W_leaf`` and its cumulative
  stash-overflow count (surfaced every round, never silent);
* ESG membership ops ride the same round stream: ``add_source`` starts a
  gained source at its Lemma-3 safe bound gamma, ``remove_source`` flushes
  (the frontier stops gating; stashed tuples drain as W rises), ``flush``
  removes every owned source so the final push empties the stash.

``LeafOut`` payloads are plain numpy (the tier's channels may cross process
boundaries); the worker loops for thread and process mode live here too so
a spawn-context child can import them top-level.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np

from repro import obs as _obs
from repro.core import scalegate
from repro.core import tuples as T

FIELDS = ("tau", "keys", "payload", "source", "valid", "is_control",
          "ctrl_epoch")


def batch_to_np(b: T.TupleBatch) -> Dict[str, np.ndarray]:
    return {f: np.asarray(getattr(b, f)) for f in FIELDS}


def np_to_batch(d: Dict[str, np.ndarray]) -> T.TupleBatch:
    return T.TupleBatch(**to_host({f: d[f] for f in FIELDS}))


def compact_np(d: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Keep only the valid lanes (host-side; output of a gate push)."""
    keep = d["valid"]
    return {f: d[f][keep] for f in FIELDS}


def empty_np(kmax: int, payload_width: int) -> Dict[str, np.ndarray]:
    return {
        "tau": np.zeros((0,), np.int32),
        "keys": np.zeros((0, kmax), np.int32),
        "payload": np.zeros((0, payload_width), np.float32),
        "source": np.zeros((0,), np.int32),
        "valid": np.zeros((0,), bool),
        "is_control": np.zeros((0,), bool),
        "ctrl_epoch": np.zeros((0,), np.int32),
    }


def concat_np(parts: Sequence[Dict[str, np.ndarray]],
              kmax: int, payload_width: int) -> Dict[str, np.ndarray]:
    parts = [p for p in parts if p["tau"].shape[0]]
    if not parts:
        return empty_np(kmax, payload_width)
    return {f: np.concatenate([p[f] for p in parts]) for f in FIELDS}


def pad_np(d: Dict[str, np.ndarray], n: int) -> Dict[str, np.ndarray]:
    """Pad to exactly ``n`` lanes with invalid filler (static jit shapes)."""
    have = d["tau"].shape[0]
    assert have <= n, (have, n)
    if have == n:
        return d
    pad = n - have
    out = {}
    for f in FIELDS:
        a = d[f]
        shape = (pad,) + a.shape[1:]
        out[f] = np.concatenate([a, np.zeros(shape, a.dtype)])
    return out


@functools.lru_cache(maxsize=None)
def host_device():
    """The device the tier's gates live and merge on: the host's CPU, so
    that a merge and its read-back never queue behind the step on the
    accelerator.  None in a process with no CPU backend (a
    ``JAX_PLATFORMS`` naming only the accelerator): the gates then keep
    the default device, as the one warning says."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        warnings.warn("no CPU backend in this process: the ingest tier's "
                      "ScaleGate merges run on the default device",
                      RuntimeWarning, stacklevel=2)
        return None


def gate_backend() -> Optional[str]:
    """The merge's kernel backend, the one the gates' device takes:
    ``xla`` on the host CPU, the dispatcher's default elsewhere."""
    return "xla" if host_device() is not None else None


def to_host(tree):
    """Commit ``tree`` (numpy or jax arrays) to the gates' device; jitted
    pushes over committed arguments run there."""
    return jax.device_put(tree, host_device())


def on_host():
    """Context in which this thread's new jax arrays are made on the gates'
    device (membership masks, gammas, fresh or restored gate state)."""
    return jax.default_device(host_device())


@functools.lru_cache(maxsize=None)
def _jit_push(backend: Optional[str]):
    """One jitted ``scalegate.push`` per backend, shared by every gate (the
    jit cache then dedups compilations across leaves by shape)."""
    return jax.jit(functools.partial(scalegate.push, backend=backend))


@dataclasses.dataclass
class LeafOut:
    """One leaf's contribution to one root round (picklable: numpy only)."""
    leaf_id: int
    round_id: int
    ready: Dict[str, np.ndarray]   # compacted ready tuples, tau-sorted
    wmark: int                     # reported leaf watermark W_leaf
    overflow: int                  # cumulative leaf stash-overflow count
    final: bool = False            # last message (leaf flushed and left)
    # cross-process observability payload (drained child spans/counters/
    # events piggybacking on the round stream); None in thread mode, where
    # the leaf shares the parent's registry directly
    obs: Optional[Dict] = None

    @property
    def n_ready(self) -> int:
        return int(self.ready["tau"].shape[0])


@dataclasses.dataclass
class LeafSnap:
    """One leaf's answer to a snapshot round: its full exported gate state
    (picklable numpy only — crosses process channels like any LeafOut).
    Riding the same round stream as tick messages is what pins the snapshot
    to an exact tick boundary: the state is captured after the leaf pushed
    round ``round_id - 1`` and before it sees the next tick."""
    leaf_id: int
    round_id: int
    state: Dict


class LeafGate:
    """The pure leaf state machine; drivable inline, from a thread, or from
    a child process (see the worker loops below)."""

    def __init__(self, leaf_id: int, n_sources: int, owned: np.ndarray,
                 cap: int, kmax: int, payload_width: int,
                 chunk: Optional[int] = None, state: Optional[Dict] = None):
        self.leaf_id = leaf_id
        self.n_sources = n_sources
        self.kmax = kmax
        self.payload_width = payload_width
        self.backend = gate_backend()
        # chunk width: combined merge size is cap + chunk; keeping it a
        # power of two lets merge_order take the bitonic-kernel path
        self.chunk = chunk or cap
        with on_host():
            if state is not None:
                # restore: stash / frontier / active mask all come from the
                # snapshot (the owned mask is part of the exported state)
                st = scalegate.import_np(state)
            else:
                st = scalegate.init_scalegate(
                    n_sources, cap, kmax, payload_width,
                    active=np.asarray(owned, bool))
            self.state = to_host(st)
        self._push = _jit_push(self.backend)

    def export_state(self) -> Dict:
        """Picklable numpy snapshot of the gate (stash + frontier +
        overflow); ``LeafGate(..., state=...)`` restores it exactly."""
        return scalegate.export_np(self.state)

    # -- per-round work ------------------------------------------------------
    def push_round(self, round_id: int, slice_np: Optional[Dict] = None,
                   final: bool = False) -> LeafOut:
        """Push this round's routed tuples (possibly none) and report.
        Every chunk is pushed before any result is read back, so the
        round's one wait for its merges is the span ``leaf.fetch`` (on the
        host CPU, a copy once the merge is done)."""
        outs = []
        lanes = 0 if slice_np is None else slice_np["tau"].shape[0]
        off = 0
        while True:
            n = min(self.chunk, lanes - off)
            if slice_np is None or n <= 0:
                chunk = pad_np(empty_np(self.kmax, self.payload_width),
                               self.chunk)
            else:
                chunk = pad_np({f: slice_np[f][off:off + n] for f in FIELDS},
                               self.chunk)
            self.state, out = self._push(self.state, np_to_batch(chunk))
            outs.append(out)
            off += self.chunk
            if off >= lanes:
                break
        with _obs.span("leaf.fetch", round=round_id):
            fetched = [batch_to_np(out) for out in outs]
            wmark = int(self.state.wmark.value())
            overflow = int(self.state.overflow)
        ready = concat_np([compact_np(d) for d in fetched], self.kmax,
                          self.payload_width)
        return LeafOut(self.leaf_id, round_id, ready, wmark=wmark,
                       overflow=overflow, final=final)

    # -- ESG membership ------------------------------------------------------
    def _mask(self, src: int):
        m = np.zeros((self.n_sources,), bool)
        m[src] = True
        return to_host(m)

    def add_source(self, src: int, gamma: int) -> None:
        with on_host():
            self.state = scalegate.add_sources(self.state, self._mask(src),
                                               gamma)

    def remove_source(self, src: int) -> None:
        self.state = scalegate.remove_sources(self.state, self._mask(src))

    def flush_all(self) -> None:
        self.state = scalegate.remove_sources(
            self.state, to_host(np.ones((self.n_sources,), bool)))

    def apply(self, ops: Sequence[Tuple]) -> bool:
        """Apply a reconfiguration op list; returns True when this leaf is
        leaving (its subsequent push is its flush + final message)."""
        leaving = False
        for op in ops:
            if op[0] == "add_source":
                self.add_source(op[1], op[2])
            elif op[0] == "remove_source":
                self.remove_source(op[1])
            elif op[0] == "flush":
                self.flush_all()
                leaving = True
            else:                                     # pragma: no cover
                raise ValueError(f"unknown leaf op {op!r}")
        return leaving


def run_gate_loop(gate: LeafGate, recv, send, ship_obs: bool = False) -> None:
    """The worker protocol: drive ``gate`` from ``recv()`` messages until a
    stop/flush; shared verbatim by thread and process workers.

    Messages: ``("tick", round, slice_np)`` | ``("cmd", round, ops)`` |
    ``("snap", round)`` | ``("stop",)``.  Every tick/cmd/snap message
    produces exactly one answer (``LeafOut`` / ``LeafSnap``) via ``send`` —
    the root's round barrier counts on it.

    ``ship_obs=True`` (process workers only) attaches the child's drained
    observability payload to each outgoing ``LeafOut``; thread workers
    share the parent's registry and must NOT ship (double-counting).
    """
    from repro.io.queues import QueueClosed

    def answer(out: LeafOut) -> None:
        _obs.counter_inc("leaf.rounds")
        _obs.counter_inc("leaf.tuples_ready", out.n_ready)
        _obs.event("leaf_push", leaf_id=out.leaf_id, round_id=out.round_id,
                   n_ready=out.n_ready, wmark=out.wmark,
                   overflow=out.overflow, final=out.final)
        if ship_obs:
            out.obs = _obs.drain_payload()
        send(out)

    while True:
        try:
            msg = recv()
        except QueueClosed:
            break
        kind = msg[0]
        if kind == "stop":
            break
        if kind == "tick":
            tl = _obs.exemplars()
            if tl is not None and msg[2] is not None:
                s = msg[2]
                tl.scan(s["source"], s["tau"],
                        s["valid"] & ~s["is_control"], "leaf_push")
            with _obs.span("leaf.push", round=msg[1]):
                out = gate.push_round(msg[1], msg[2])
            answer(out)
        elif kind == "cmd":
            leaving = gate.apply(msg[2])
            with _obs.span("leaf.push", round=msg[1]):
                out = gate.push_round(msg[1], None, final=leaving)
            answer(out)
            if leaving:
                break
        elif kind == "snap":
            send(LeafSnap(gate.leaf_id, msg[1], gate.export_state()))
        else:                                         # pragma: no cover
            raise ValueError(f"unknown message {msg!r}")


def process_worker_main(cfg: Dict, in_q, out_q) -> None:
    """Child-process body, started through ``repro.host_worker.run``, which
    has already pinned this process's JAX to the CPU and its kernel backend
    to ``xla`` (an ingest host is a CPU host; the chip belongs to the
    parent).  ``cfg`` carries the LeafGate constructor args as picklable
    values, and all channel payloads are numpy.  Mirrors ``run_gate_loop``
    over the mp queues; the first event it records, ``leaf_start``, names
    the platform and backend the leaf runs on.
    """
    import jax

    from repro.kernels import dispatch
    from repro.ingest.channels import MP_CLOSE
    from repro.io.queues import QueueClosed

    ship_obs = False
    if cfg.get("obs"):
        # the child gets its own Obs (same config as the parent's) and
        # ships drained payloads back on the round stream
        _obs.install(_obs.ObsConfig.from_dict(cfg["obs"]))
        ship_obs = True

    gate = LeafGate(cfg["leaf_id"], cfg["n_sources"],
                    np.asarray(cfg["owned"], bool), cfg["cap"], cfg["kmax"],
                    cfg["payload_width"], chunk=cfg.get("chunk"),
                    state=cfg.get("state"))
    _obs.event("leaf_start", leaf_id=gate.leaf_id,
               platform=jax.devices()[0].platform,
               backend=dispatch.resolve(gate.backend))

    def recv():
        msg = in_q.get()
        if msg == MP_CLOSE:
            raise QueueClosed
        return msg

    run_gate_loop(gate, recv, out_q.put, ship_obs=ship_obs)
