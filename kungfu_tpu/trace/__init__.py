"""kftrace — cluster-wide structured tracing and flight recorder.

The reference runtime treats online observability as a first-class
subsystem (srcs/go/monitor/, session/monitoring.go); this package is
the tracing half of that plane for the TPU port.  It replaces the bare
``(ts, name)`` tuples of :mod:`kungfu_tpu.utils.trace` with structured
records — monotonic timestamp plus a wall-clock anchor, rank, pid,
step, membership version, category, duration and free-form attrs —
held in a bounded ring buffer (a *flight recorder*) with an optional
per-worker JSONL sink.

Instrumented call sites follow the kfchaos discipline: :func:`event`
and :func:`span` are no-ops behind a SINGLE module-global ``None``
check unless a recorder is armed, so production pays one predicate per
site (tests/test_kftrace.py pins the bound the same way
tests/test_chaos.py pins ``chaos.point``'s).

Arming happens either in-process via :func:`arm` or by environment,
read once at import (the kfchaos idiom — launcher workers inherit it):

- ``KFT_TRACE=1`` — ring buffer only (flight recorder for crash dumps)
- ``KFT_TRACE_DIR=/path`` — ring buffer + a per-worker JSONL stream
  ``kftrace.r<rank>.<pid>.jsonl`` under that directory, plus a crash
  dump handler (:mod:`.crashdump`) that writes the recorder tail on an
  unhandled exception or SIGTERM.
- ``KFT_TRACE_RING=N`` — ring capacity (default 4096 events).

Every JSONL stream begins with an *anchor* record pairing one wall
clock reading with one monotonic reading from the same instant; the
merger CLI (:mod:`.merge`, ``tools/kftrace_merge.py``) uses the
anchors to align streams from different processes onto one wall-clock
timeline and emits Chrome-trace JSON for Perfetto / chrome://tracing.
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Dict, List, Optional

from ..utils import knobs

__all__ = [
    "Recorder", "arm", "disarm", "armed", "event", "span", "tail",
    "dump", "recorder",
    "ENV_RING", "ENV_DIR", "ENV_ENABLE", "DEFAULT_RING",
]

ENV_ENABLE = "KFT_TRACE"
ENV_DIR = "KFT_TRACE_DIR"
ENV_RING = "KFT_TRACE_RING"
DEFAULT_RING = 4096


def _env_rank() -> Optional[int]:
    """This worker's rank from the launcher env ABI, parsed without
    importing :mod:`kungfu_tpu.launcher` (tracing must stay importable
    from every layer, including the ones launcher.env imports)."""
    spec = knobs.raw("KFT_SELF_SPEC") or ""
    peers = knobs.raw("KFT_INIT_PEERS") or ""
    if not spec or not peers:
        return None
    try:
        return peers.split(",").index(spec)
    except ValueError:
        return None


class Recorder:
    """Bounded in-memory event ring + optional JSONL sink.

    The wall/monotonic anchor pair is captured once at construction;
    monotonic timestamps survive NTP steps (the PR-1 discipline) and
    the anchor lets the merger place them on a wall-clock axis.
    """

    def __init__(self, sink_dir: Optional[str] = None,
                 capacity: int = DEFAULT_RING,
                 rank: Optional[int] = None):
        self.anchor_wall = time.time()
        self.anchor_mono = time.perf_counter()
        self.pid = os.getpid()
        self.rank = rank if rank is not None else _env_rank()
        self._ring: collections.deque = collections.deque(
            maxlen=max(1, int(capacity)))
        self._lock = threading.Lock()
        self._sink = None
        self.sink_path: Optional[str] = None
        if sink_dir:
            os.makedirs(sink_dir, exist_ok=True)
            tag = (f"r{self.rank}" if self.rank is not None else "rx")
            self.sink_path = os.path.join(
                sink_dir, f"kftrace.{tag}.{self.pid}.jsonl")
            self._sink = open(self.sink_path, "a")
            self._sink.write(json.dumps(self._anchor_record()) + "\n")
            self._sink.flush()

    def _anchor_record(self) -> dict:
        return {"kind": "anchor", "wall": self.anchor_wall,
                "mono": self.anchor_mono, "pid": self.pid,
                "rank": self.rank}

    def record(self, name: str, category: str = "event",
               rank: Optional[int] = None, step: Optional[int] = None,
               version: Optional[int] = None,
               ts: Optional[float] = None, dur: Optional[float] = None,
               attrs: Optional[dict] = None,
               wait: bool = True) -> Optional[dict]:
        """Append one structured event (and stream it to the sink).

        ``wait=False`` returns None, recording nothing, where another
        frame holds the recorder's lock: for a caller that may interrupt
        this very method in its own thread (a ``gc.callbacks`` entry)."""
        ev: Dict = {"ts": time.perf_counter() if ts is None else ts,
                    "name": name, "cat": category,
                    "pid": self.pid,
                    "rank": self.rank if rank is None else rank}
        if step is not None:
            ev["step"] = step
        if version is not None:
            ev["version"] = version
        if dur is not None:
            ev["dur"] = dur
        if attrs:
            ev["attrs"] = attrs
        if not self._lock.acquire(wait):
            return None
        try:
            self._ring.append(ev)
            if self._sink is not None:
                # flush (not fsync) per line: the bytes reach the OS, so
                # they survive SIGKILL of this process; only a host
                # crash loses the tail — the chaos JOURNAL (which drives
                # correctness checks, not timelines) is the fsync'd tier
                self._sink.write(json.dumps(ev) + "\n")
                self._sink.flush()
        finally:
            self._lock.release()
        return ev

    def tail(self, n: Optional[int] = None) -> List[dict]:
        with self._lock:
            evs = list(self._ring)
        return evs if n is None else evs[-n:]

    def dump(self, path: str) -> int:
        """Write anchor + the current ring tail as JSONL; returns the
        number of events written (the crash-dump entry point)."""
        with self._lock:
            evs = list(self._ring)
        with open(path, "w") as f:
            f.write(json.dumps(self._anchor_record()) + "\n")
            for ev in evs:
                f.write(json.dumps(ev) + "\n")
        return len(evs)

    def close(self) -> None:
        with self._lock:
            if self._sink is not None:
                self._sink.close()
                self._sink = None


_rec: Optional[Recorder] = None


class _NullSpan:
    """Shared do-nothing context: the disarmed fast path allocates
    nothing (``span(...)`` returns this singleton)."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("_rec", "_name", "_cat", "_rank", "_step", "_version",
                 "_attrs", "_t0")

    def __init__(self, rec, name, cat, rank, step, version, attrs):
        self._rec = rec
        self._name = name
        self._cat = cat
        self._rank = rank
        self._step = step
        self._version = version
        self._attrs = attrs

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def set(self, **kw) -> None:
        """Attach attrs discovered inside the scope (payload sizes,
        outcome codes).  The disarmed path never reaches here — span()
        returned the null context, whose ``__enter__`` yields None."""
        if self._attrs is None:
            self._attrs = {}
        else:
            self._attrs = dict(self._attrs)
        self._attrs.update(kw)

    def __exit__(self, etype, exc, tb):
        dur = time.perf_counter() - self._t0
        attrs = self._attrs
        if etype is not None:
            # the failed path records too (the utils.trace_scope bug
            # class): a resize that died mid-phase still shows its span
            attrs = dict(attrs or ())
            attrs["error"] = etype.__name__
        self._rec.record(self._name, self._cat, rank=self._rank,
                         step=self._step, version=self._version,
                         ts=self._t0, dur=dur, attrs=attrs)
        return False


def event(name: str, *, category: str = "event",
          rank: Optional[int] = None, step: Optional[int] = None,
          version: Optional[int] = None, dur: Optional[float] = None,
          attrs: Optional[dict] = None) -> None:
    """Record one instant event.  No-op behind a single module-global
    check unless a recorder is armed (the ``chaos.point`` discipline)."""
    rec = _rec
    if rec is None:
        return
    rec.record(name, category, rank=rank, step=step, version=version,
               dur=dur, attrs=attrs)


def span(name: str, *, category: str = "span",
         rank: Optional[int] = None, step: Optional[int] = None,
         version: Optional[int] = None, attrs: Optional[dict] = None):
    """A timed scope: ``with span("elastic.resize", rank=r): ...``.
    Disarmed, returns a shared null context (one predicate, zero
    allocation); armed, records the duration on success AND failure
    (failures carry ``attrs.error``)."""
    rec = _rec
    if rec is None:
        return _NULL_SPAN
    return _Span(rec, name, category, rank, step, version, attrs)


def arm(sink_dir: Optional[str] = None, capacity: Optional[int] = None,
        rank: Optional[int] = None) -> Recorder:
    """Install a recorder for this process and return it."""
    global _rec
    if capacity is None:
        capacity = knobs.get(ENV_RING)
    _rec = Recorder(sink_dir=sink_dir, capacity=capacity, rank=rank)
    return _rec


def disarm() -> None:
    """Close any sink and return every site to the no-op fast path."""
    global _rec
    rec, _rec = _rec, None
    if rec is not None:
        rec.close()


def armed() -> bool:
    return _rec is not None


def recorder() -> Optional[Recorder]:
    return _rec


def tail(n: Optional[int] = None) -> List[dict]:
    """The flight-recorder tail (empty when disarmed)."""
    rec = _rec
    return rec.tail(n) if rec is not None else []


def dump(path: str) -> int:
    """Dump the flight recorder to ``path``; 0 when disarmed."""
    rec = _rec
    return rec.dump(path) if rec is not None else 0


def _arm_from_env() -> None:
    """Read KFT_TRACE / KFT_TRACE_DIR exactly once, at import (the
    kfchaos idiom: launcher workers inherit the env; a process setting
    it after import stays disarmed unless it calls :func:`arm`)."""
    sink = knobs.raw(ENV_DIR) or ""
    on = bool(knobs.get(ENV_ENABLE))
    if not sink and not on:
        return
    arm(sink_dir=sink or None)
    if sink:
        from . import crashdump
        crashdump.install(sink)


_arm_from_env()
