"""Tracing / profiling.

Reference analogues (SURVEY.md §5): the compile-time ``TRACE_SCOPE``
macros around collective calls (include/kungfu/utils/trace.hpp:1-16,
enabled by KUNGFU_ENABLE_TRACE) and the elastic hook's ``_log_event``
timestamps (hooks/elastic.py:49-56).

TPU-native form: scopes are runtime-gated by ``KFT_CONFIG_ENABLE_TRACE``
(same toggle tier as the reference's env) and, when jax is tracing a
profile, annotate the XLA timeline via ``jax.profiler.TraceAnnotation`` —
so the same scope names appear in host-side stats and in XProf/TensorBoard
device traces.  ``start_capture``/``stop_capture`` wrap ``jax.profiler``
for on-demand device trace dumps.

This module is the lightweight per-process aggregate view (scope call
counts/totals, an event mark list); the STRUCTURED per-event stream —
rank/pid/step/version-tagged records in a bounded flight recorder with
a JSONL sink and a cross-worker merger — is :mod:`kungfu_tpu.trace`
(kftrace, docs/monitoring.md).  Scopes and events here mirror into
kftrace when it is armed, so both views agree.
"""
from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
from typing import Deque, Dict, List, Optional, Tuple

from .. import trace as _kftrace
from . import knobs

ENABLE_ENV = "KFT_CONFIG_ENABLE_TRACE"

# events are always-on (the elastic protocol logs them unconditionally)
# so the list must be bounded: a long-running worker logging resize
# events forever must not leak memory.  The cap is generous — resize
# events arrive at human timescales.
EVENTS_LIMIT = 65536

_lock = threading.Lock()
_scopes: Dict[str, Tuple[int, float]] = {}   # name -> (count, total_s)
_events: Deque[Tuple[float, str]] = collections.deque(maxlen=EVENTS_LIMIT)


def enabled() -> bool:
    return bool(knobs.get(ENABLE_ENV))


@contextlib.contextmanager
def trace_scope(name: str):
    """Time a scope (reference TRACE_SCOPE).  No-op unless enabled.

    The duration is recorded on the EXCEPTION path too — a scope that
    died mid-flight is accounted under ``<name> [failed]`` (losing the
    sample entirely would hide exactly the slow-then-crashed cases a
    trace exists to show)."""
    if not enabled():
        yield
        return
    import jax
    t0 = time.perf_counter()
    failed = False
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    except BaseException:
        failed = True
        raise
    finally:
        dt = time.perf_counter() - t0
        key = f"{name} [failed]" if failed else name
        with _lock:
            c, tot = _scopes.get(key, (0, 0.0))
            _scopes[key] = (c + 1, tot + dt)
        _kftrace.event(name, category="scope", dur=dt,
                       attrs={"failed": True} if failed else None)


def scope_stats() -> Dict[str, Tuple[int, float]]:
    """{name: (count, total_seconds)} accumulated by trace_scope."""
    with _lock:
        return dict(_scopes)


def log_event(name: str) -> float:
    """Timestamped event mark (reference _log_event); always on — events
    are cheap and the elastic protocol logs them unconditionally.

    Timestamps are ``time.perf_counter()`` — a monotonic timebase, so
    intervals between events survive NTP steps; they order and diff
    against each other, not against wall-clock log lines.  Each mark is
    mirrored into the kftrace flight recorder (one predicate when
    disarmed), where it also gains rank/pid and the wall-clock anchor."""
    ts = time.perf_counter()
    with _lock:
        _events.append((ts, name))
    _kftrace.event(name, category="event")
    return ts


def events() -> List[Tuple[float, str]]:
    with _lock:
        return list(_events)


def reset() -> None:
    with _lock:
        _scopes.clear()
        _events.clear()


# one device capture at a time: jax.profiler raises RuntimeError on a
# second start_trace, and a failed start used to leak that exception to
# whoever asked for a profile (the /profile endpoint must answer "busy",
# not die).  The guard holds the active logdir; failures are COUNTED on
# the monitor (kungfu_tpu_profile_failures_total) so they stay visible
# without taking down the caller.
_capture_lock = threading.Lock()
_capture_dir: Optional[str] = None


def _count_capture_failure(op: str) -> None:
    from ..monitor import get_monitor
    get_monitor().inc("kungfu_tpu_profile_failures_total",
                      labels={"op": op})


def capturing() -> Optional[str]:
    """The active capture's logdir, or None."""
    with _capture_lock:
        return _capture_dir


def _device_only():
    """Profiler options with the host and Python tracers off, as the
    benchmark's traced runs have them (perf/loop.py).  With the host
    tracer on, the runtime's transfer threads wrote 30 million events
    for 30 ResNet-50 steps: 0.94 GB, over a minute in ``stop_trace`` and
    a device that stalled while they drained (PERF.md, PR 25 (a))."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    return options


def start_capture(logdir: str) -> Optional[str]:
    """Begin an XLA device trace (view in XProf/TensorBoard): device
    events only, see :func:`_device_only`.

    Idempotent and exception-safe: returns the logdir on success, None
    when a capture is already running or jax.profiler refused (counted
    via Monitor, never raised — a profile request must degrade to "no
    capture", not crash the serving thread)."""
    global _capture_dir
    import jax
    with _capture_lock:
        if _capture_dir is not None:
            _count_capture_failure("start-busy")
            return None
        try:
            jax.profiler.start_trace(logdir,
                                     profiler_options=_device_only())
        except Exception:
            _count_capture_failure("start")
            return None
        _capture_dir = logdir
        return logdir


def stop_capture() -> Optional[str]:
    """End the active capture; returns its logdir, or None when nothing
    was running (idempotent — a double stop is a no-op, not a
    RuntimeError out of jax.profiler)."""
    global _capture_dir
    import jax
    with _capture_lock:
        if _capture_dir is None:
            return None
        logdir, _capture_dir = _capture_dir, None
        try:
            jax.profiler.stop_trace()
        except Exception:
            _count_capture_failure("stop")
            return None
        return logdir


@contextlib.contextmanager
def capture(logdir: str):
    """Capture for the duration of the block; yields the logdir (None
    when another capture already owns the profiler — this block then
    must NOT stop it on exit)."""
    started = start_capture(logdir)
    try:
        yield started
    finally:
        if started is not None:
            stop_capture()
