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

import os
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
    return cache_dir


class CompileCounter:
    """Counts, from construction on, the programs XLA compiled in this
    process and the ones the persistent cache supplied instead (jax's
    own monitoring events).  A warm run of an unchanged command should
    read ``compiled == 0``."""

    # recorded once per program jax asks the backend for, cached or not
    _REQUEST = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self._requests = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    @property
    def compiled(self) -> int:
        return self._requests - self.cache_hits

    def _on_duration(self, name, _secs, **_kw):
        if name == self._REQUEST:
            self._requests += 1

    def _on_event(self, name, **_kw):
        if name == self._HIT:
            self.cache_hits += 1
