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
"""
from __future__ import annotations

import collections
import os
import time
from typing import Optional

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
    which later call compiled again."""

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

    def __init__(self):
        import jax
        global _current
        self._requests = 0
        self.cache_hits = 0
        self.records = collections.deque(maxlen=self.MAX_RECORDS)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        _current = self

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

    def _on_duration(self, name, secs, **_kw):
        if name in self.STAGE_EVENTS:
            self.records.append((name, secs, time.perf_counter_ns()))
            if name == self.REQUEST:
                self._requests += 1

    def _on_event(self, name, **_kw):
        if name == self._HIT:
            self.cache_hits += 1


_current: Optional[CompileCounter] = None


def current_counter() -> Optional[CompileCounter]:
    """The newest :class:`CompileCounter` of this process, or None."""
    return _current
