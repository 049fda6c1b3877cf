"""Persistent XLA compilation cache wiring.

SURVEY §7 names resize-triggers-recompile as the dominant engineering
risk of elastic training on XLA: the reference's resize costs ~1 barrier
(srcs/go/kungfu/peer/peer.go:144-166 rebuilds a session, no compilation),
ours costs a recompile at every previously-unseen cluster size — and
every fresh process compiles its whole program set from nothing.  Two
mitigations stack:

1. in-process: ElasticTrainer caches compiled steps per size, so
   oscillating schedules (4→8→4…) recompile once per distinct size;
2. across processes/restarts (this module): jax's persistent
   compilation cache makes the recompile a disk hit.

Where the cache lives is decided OUTSIDE the code:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax itself reads it at import and
  the cache is there, on every backend.  This module sets no directory.
- unset, accelerator backend: ``<checkout>/.jax_cache`` — derived from
  this package's location, so every process of one checkout shares it
  and two runs of one command find each other's programs.
- unset, CPU backend: off.  XLA:CPU AOT blobs log a harmless-but-
  alarming cpu_aot_loader "SIGILL" error on every cached load, and the
  tests compile little enough not to need it.

Call :func:`enable_compile_cache` once per process, after the backend is
chosen (after ``jax.distributed.initialize`` in multi-process workers)
and before the first jit.  Idempotent.

:class:`CompileCounter` is also the process's host recorder: besides
jax's events it keeps the garbage collector's pauses and the input
pipeline's staging and hand-outs (:mod:`kungfu_tpu.data.pipeline`), all
on ``time.perf_counter_ns()``, so that what the host did in a stretch of
a training loop can be read afterwards from one place.
"""
from __future__ import annotations

import collections
import gc
import os
import time
from typing import NamedTuple, Optional

from .. import trace as _kftrace

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn jax's persistent compilation cache on and make it keep every
    program.  Returns the directory in use, or None when the cache is
    off (CPU backend and no ``JAX_COMPILATION_CACHE_DIR``).

    jax's default thresholds skip programs that compiled in under a
    second; a resize or a respawn pays for those too, so they are
    lowered to zero unless the matching ``JAX_PERSISTENT_CACHE_*``
    variable says otherwise."""
    import jax
    cache_dir = os.environ.get(CACHE_DIR_ENV)
    if not cache_dir:
        if jax.default_backend() == "cpu":
            return None
        cache_dir = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
    if "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES" not in os.environ:
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # a device trace is read by the names the executable carries
    # (jax.named_scope and the kernels' names: docs/monitoring.md), and
    # jax's default key leaves them out: a step whose scopes changed
    # would load the executable compiled before the change, old names and
    # all.  With them in the key, so are source positions: a program
    # whose traced lines moved compiles once more.
    if "JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY" not in os.environ:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
    return cache_dir


class HostRecord(NamedTuple):
    """One interval the host recorder kept, on ``time.perf_counter_ns()``.

    ``kind`` is one of :class:`CompileCounter`'s: its ``JAX_KINDS``,
    ``GC``, ``STAGE`` or ``HANDOUT``.  ``name`` is the
    function jax traced, lowered or asked the backend for (empty for a
    cache retrieval: its program is that of the request that follows it),
    ``gen0`` to ``gen2`` for a collection, empty for the feed.  ``seq``
    joins a batch's staging to its hand-out (-1 elsewhere); ``value`` is
    what a collection collected, or the queue's depth a hand-out found."""
    kind: str
    name: str
    start_ns: int
    end_ns: int
    seq: int = -1
    value: int = 0

    @property
    def label(self) -> str:
        return f"{self.kind} {self.name}" if self.name else self.kind


class CompileCounter:
    """Counts, from construction on, the programs XLA compiled in this
    process and the ones the persistent cache supplied instead (jax's
    own monitoring events).  A warm run of an unchanged command should
    read ``compiled == 0``.

    Beside the two counts it keeps what jax says each stage of getting a
    program took: ``records`` holds one ``(event, seconds, arrived_ns)``
    per event of :data:`STAGE_EVENTS`, ``arrived_ns`` on
    ``time.perf_counter_ns()``, the newest :data:`MAX_RECORDS` of them.
    :meth:`seconds` and :meth:`compile_seconds` sum them up to a moment
    of that clock, so a reader can tell what set-up spent on tracing,
    lowering, loading from the cache and compiling, and the records say
    which later call compiled again.

    It is also the process's host recorder.  ``host`` holds the newest
    :data:`MAX_RECORDS` :class:`HostRecord` intervals of every kind:
    jax's four stages with the function's name, the
    garbage collector's pauses (while this is the current counter: every
    collection of generations 1 and 2, and of generation 0 those that
    took :data:`QUICK_GC_NS` or more), and what :class:`~kungfu_tpu.data.
    pipeline.Prefetcher` does for each batch.  While kftrace
    (:mod:`kungfu_tpu.trace`) is armed, the current counter mirrors each
    record there too, under the categories ``host.jax``, ``host.gc`` and
    ``host.feed``."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    # recorded once per program jax asks the backend for, cached or not:
    # it spans the cache's key, the retrieval and, on a miss, XLA itself
    REQUEST = "/jax/core/compile/backend_compile_duration"
    # recorded inside a request the cache answered, so just before it
    RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
    STAGE_EVENTS = (TRACE, LOWER, REQUEST, RETRIEVAL)
    MAX_RECORDS = 1 << 16
    _HIT = "/jax/compilation_cache/cache_hits"

    JAX_KINDS = {TRACE: "jax.trace", LOWER: "jax.lower",
                 REQUEST: "jax.request", RETRIEVAL: "jax.retrieval"}
    GC = "gc"
    # the producer's work for a batch: the source's next and the placement
    STAGE = "feed.stage"
    # the consumer's wait inside next(), ending as the batch is handed out
    HANDOUT = "feed.handout"
    # what holds the host's Python back: collections and getting programs
    PAUSES = (GC, *JAX_KINDS.values())

    def __init__(self):
        import jax
        global _current
        self._requests = 0
        self.cache_hits = 0
        self.records = collections.deque(maxlen=self.MAX_RECORDS)
        self.host = collections.deque(maxlen=self.MAX_RECORDS)
        # records a collection could not mirror: kftrace's lock was held
        self._unmirrored = collections.deque(maxlen=self.MAX_RECORDS)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        _current = self
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)

    @property
    def compiled(self) -> int:
        return self._requests - self.cache_hits

    def seconds(self, *events: str, until_ns: Optional[int] = None) -> float:
        """Time during which one of ``events`` was under way, over the
        records that arrived by ``until_ns`` (all of them without it).
        An event arrives at its end, so a record spans ``seconds`` back
        from ``arrived_ns``; the spans' union is taken, not their sum,
        because jax reports the tracing of every inner ``jit`` inside its
        caller's (a thousand events for one step, a third of their sum
        counted twice) and traces again while it lowers."""
        total, open_until = 0.0, float("-inf")
        for start, end in sorted(
                (at / 1e9 - secs, at / 1e9) for name, secs, at in self.records
                if name in events and (until_ns is None or at <= until_ns)):
            if end > open_until:
                total += end - max(start, open_until)
                open_until = end
        return total

    def compile_seconds(self, until_ns: Optional[int] = None) -> float:
        """Summed seconds of the requests the cache did not answer (no
        retrieval stands before them): what compiling cost."""
        total, before = 0.0, None
        for name, secs, at in self.records:
            if (name == self.REQUEST and before != self.RETRIEVAL
                    and (until_ns is None or at <= until_ns)):
                total += secs
            before = name
        return total

    def requests(self, since_ns: int = 0,
                 until_ns: Optional[int] = None) -> list:
        """``(function, seconds, arrived_ns, from_cache)`` for each program
        jax asked the backend for, arrived in ``[since_ns, until_ns]``;
        ``from_cache`` where a retrieval is the jax record just before the
        request's (a hit's retrieval arrives inside its request)."""
        request = self.JAX_KINDS[self.REQUEST]
        retrieval = self.JAX_KINDS[self.RETRIEVAL]
        out, before = [], None
        for r in list(self.host):
            if not r.kind.startswith("jax."):
                continue
            if (r.kind == request and since_ns <= r.end_ns
                    and (until_ns is None or r.end_ns <= until_ns)):
                out.append((r.name, (r.end_ns - r.start_ns) / 1e9, r.end_ns,
                            before == retrieval))
            before = r.kind
        return out

    def on_profile_clock(self, offset_ns: int, profile_start_ns: int,
                         since_ns: int = 0) -> list:
        """``(label, start_ns, duration_ns)`` of the host records that
        began at or after ``since_ns``, with starts counted from a
        profiler trace's start as the trace's own events are.
        ``offset_ns`` is what to add to ``perf_counter_ns`` to get the
        time of day (``time.time_ns() - time.perf_counter_ns()``), the
        clock of the trace's ``profile_start_time``."""
        return [(r.label, r.start_ns + offset_ns - profile_start_ns,
                 r.end_ns - r.start_ns)
                for r in list(self.host) if r.start_ns >= since_ns]

    def add(self, kind: str, name: str, start_ns: int, end_ns: int,
            seq: int = -1, value: int = 0) -> None:
        """Keep one interval of the host's (any thread may call it)."""
        record = HostRecord(kind, name, start_ns, end_ns, seq, value)
        self.host.append(record)
        rec = _kftrace.recorder()
        if rec is not None and self is _current:
            while True:
                try:
                    held = self._unmirrored.popleft()
                except IndexError:
                    break
                _mirror(rec, held, True)
            _mirror(rec, record, True)

    def _collected(self, gen: int, start_ns: int, end_ns: int,
                   collected: int) -> None:
        # inside the collector: kftrace's lock may be held by the frame
        # the collection interrupted, so the mirror does not wait for it
        record = HostRecord(self.GC, _GEN_NAMES[gen], start_ns, end_ns,
                            -1, collected)
        self.host.append(record)
        rec = _kftrace.recorder()
        if rec is not None and not _mirror(rec, record, False):
            self._unmirrored.append(record)

    def _on_duration(self, name, secs, fun_name="", **_kw):
        if name in self.STAGE_EVENTS:
            at = time.perf_counter_ns()
            self.records.append((name, secs, at))
            if name == self.REQUEST:
                self._requests += 1
            self.add(self.JAX_KINDS[name], fun_name, at - int(secs * 1e9), at)

    def _on_event(self, name, **_kw):
        if name == self._HIT:
            self.cache_hits += 1


def _mirror(rec, record: HostRecord, wait: bool) -> bool:
    """One host record into kftrace's recorder ``rec``; False where it
    would have had to wait for the recorder's lock and ``wait`` is not
    set."""
    kind = record.kind
    if kind == CompileCounter.GC:
        attrs = {"collected": record.value}
    elif kind == CompileCounter.HANDOUT:
        attrs = {"seq": record.seq, "depth": record.value}
    elif kind == CompileCounter.STAGE:
        attrs = {"seq": record.seq}
    else:
        attrs = None
    return rec.record(record.label, "host." + kind.split(".", 1)[0],
                      ts=record.start_ns / 1e9,
                      dur=(record.end_ns - record.start_ns) / 1e9,
                      attrs=attrs, wait=wait) is not None


_current: Optional[CompileCounter] = None

# a collection of generation 0 shorter than this is not recorded, and the
# callback then allocates nothing: there are thousands of them a second
# while jax traces, each some microseconds
QUICK_GC_NS = 1_000_000
_GEN_NAMES = ("gen0", "gen1", "gen2")
_gc_began = 0


def _on_gc(phase: str, info: dict) -> None:
    """The one ``gc.callbacks`` entry of the process, whichever counter is
    current.  Collections do not overlap (the collector runs one at a time,
    in the thread that set it off), so one start moment serves."""
    global _gc_began
    counter = _current
    if counter is None:
        return
    if phase == "start":
        _gc_began = time.perf_counter_ns()
        return
    end = time.perf_counter_ns()
    gen = info["generation"]
    if gen or end - _gc_began >= QUICK_GC_NS:
        counter._collected(gen, _gc_began, end, info["collected"])


def current_counter() -> Optional[CompileCounter]:
    """The newest :class:`CompileCounter` of this process, or None."""
    return _current
