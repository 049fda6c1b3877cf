"""kfguard RPC client — the one way control-plane HTTP leaves a process.

Before this module, nine ``fetch_config``/``put_config`` call sites each
hand-rolled their own retry/except loop: no backoff (409 storms hammered
the server), no overall deadline (a "30 s timeout" was really
N × attempt timeout), and no way to tell "server booting" from "server
gone".  :func:`call` centralises the policy:

- **per-attempt timeout + overall deadline budget** — ``deadline=None``
  means exactly one attempt (the poll-loop contract: the caller's loop
  IS the retry);
- **exponential backoff with full jitter** between attempts (decorrelates
  concurrent retriers — the AWS backoff result);
- **error classification** (:func:`classify`): conn-refused,
  404-unseeded, 409-CAS-conflict, 5xx, timeout, bad-response.  4xx
  responses PROVE the server is alive and never trip the breaker;
- **epoch-aware response check** (:func:`note_config`): a config
  response whose version regresses within one server epoch is refused
  (:class:`RPCStaleRead`) instead of fencing workers against a reborn
  counter; an epoch CHANGE (the server lost state and says so) is
  accepted and warned once;
- **half-open circuit breaker** per server: after
  ``KFT_RPC_BREAKER_FAILS`` consecutive transport failures the breaker
  opens and calls fail in microseconds (:class:`RPCCircuitOpen`) instead
  of stalling a step-path poll for a full connect timeout; after
  ``KFT_RPC_BREAKER_COOLDOWN_S`` one probe is let through (half-open)
  and a success closes it again.

Hot-path contract (pinned by tests/test_kfguard.py): with the server
healthy, ``call`` adds one breaker dict lookup — one HTTP request, no
sleeps, no extra probes.

Every RPC exception here subclasses :class:`OSError`, the class all
existing config-server callers already treat as "transient control-plane
failure", so rerouting changed no caller's error handling.

Observability: retries count into the
``kungfu_tpu_rpc_retries_total`` counter, a finished outage sets the
``kungfu_tpu_rpc_outage_seconds`` gauge, and both emit kftrace events
(``rpc.retry``, ``rpc.outage``) on the cluster timeline.  The kfchaos
site ``rpc.attempt`` fires before every attempt (drop-rpc there
exercises the retry/backoff path deterministically).
"""
from __future__ import annotations

import random
import threading
import time
import urllib.error
import urllib.request

from . import knobs
from typing import Callable, Dict, Optional, Tuple

__all__ = [
    "call", "classify", "note_config", "last_seen", "reset",
    "Backoff", "CircuitBreaker",
    "RPCCircuitOpen", "RPCStaleRead",
]

# backoff schedule: full jitter over min(cap, base * 2^attempt)
BACKOFF_BASE_S = 0.05
BACKOFF_CAP_S = 1.0

# indirections so tests can count requests / forbid sleeps
_urlopen = urllib.request.urlopen
_sleep = time.sleep

# module stats for the hot-path micro-asserts (monotonic counters)
_STATS = {"requests": 0, "retries": 0, "sleeps": 0}


class RPCCircuitOpen(OSError):
    """The per-server circuit breaker is open: the server failed
    ``KFT_RPC_BREAKER_FAILS`` consecutive transport attempts and the
    cooldown has not elapsed.  Costs the caller microseconds, not a
    connect timeout."""


class RPCStaleRead(OSError):
    """A config response regressed the version counter within one server
    epoch — a reborn/stale server must not be trusted as current."""


def _netloc(url: str) -> str:
    # cheap scheme://host:port/... -> host:port (no urlparse allocation
    # cascade on the per-step poll path)
    rest = url.split("://", 1)[-1]
    return rest.split("/", 1)[0]


def classify(exc: BaseException) -> str:
    """Map an exception from :func:`call` onto the outage classes."""
    if isinstance(exc, urllib.error.HTTPError):
        if exc.code == 404:
            return "unseeded"
        if exc.code == 409:
            return "conflict"
        if exc.code >= 500:
            return "server-error"
        return "client-error"
    if isinstance(exc, RPCCircuitOpen):
        return "circuit-open"
    if isinstance(exc, RPCStaleRead):
        return "stale-read"
    if isinstance(exc, (TimeoutError,)) or "timed out" in str(exc):
        return "timeout"
    if isinstance(exc, (urllib.error.URLError, OSError)):
        return "conn-refused"
    return "bad-response"


# --------------------------------------------------------------- breaker
class CircuitBreaker:
    """Half-open circuit breaker for one server (host:port).

    Closed fast path is two attribute reads — no lock, no clock."""

    __slots__ = ("threshold", "cooldown", "_fails", "_open_until",
                 "_probing", "_lock")

    def __init__(self, threshold: Optional[int] = None,
                 cooldown: Optional[float] = None):
        self.threshold = int(threshold if threshold is not None
                             else knobs.get("KFT_RPC_BREAKER_FAILS"))
        self.cooldown = (cooldown if cooldown is not None
                         else knobs.get("KFT_RPC_BREAKER_COOLDOWN_S"))
        self._fails = 0
        self._open_until = 0.0
        self._probing = False
        self._lock = threading.Lock()

    def allow(self) -> bool:
        """True when an attempt may go out (closed, or the half-open
        probe slot)."""
        if self._fails < self.threshold:
            return True  # closed: the hot path
        with self._lock:
            if self._fails < self.threshold:
                return True
            if time.monotonic() >= self._open_until and not self._probing:
                self._probing = True  # half-open: exactly one probe
                return True
            return False

    def success(self) -> None:
        if self._fails or self._probing:
            with self._lock:
                self._fails = 0
                self._probing = False

    def failure(self) -> None:
        with self._lock:
            self._fails += 1
            self._probing = False
            if self._fails >= self.threshold:
                self._open_until = time.monotonic() + self.cooldown

    @property
    def is_open(self) -> bool:
        return self._fails >= self.threshold

    def probe_eta(self) -> float:
        """Seconds until the next half-open probe slot (0 when closed)."""
        if self._fails < self.threshold:
            return 0.0
        return max(0.0, self._open_until - time.monotonic())


_BREAKERS: Dict[str, CircuitBreaker] = {}
_BREAKERS_LOCK = threading.Lock()


def _breaker_for(url: str) -> CircuitBreaker:
    key = _netloc(url)
    br = _BREAKERS.get(key)  # the one dict lookup of the healthy path
    if br is None:
        with _BREAKERS_LOCK:
            br = _BREAKERS.setdefault(key, CircuitBreaker())
    return br


# ---------------------------------------------------------------- backoff
class Backoff:
    """Jittered exponential backoff for caller-level retry loops (CAS
    races in :func:`~kungfu_tpu.launcher.watch.propose_exclusion`):
    ``Backoff().sleep()`` per retry decorrelates concurrent retriers."""

    def __init__(self, base: float = BACKOFF_BASE_S,
                 cap: float = BACKOFF_CAP_S):
        self.base = base
        self.cap = cap
        self.attempt = 0

    def delay(self) -> float:
        return random.uniform(0.0, min(self.cap,
                                       self.base * (2 ** self.attempt)))

    def sleep(self) -> float:
        d = self.delay()
        self.attempt += 1
        if d > 0.0:
            _STATS["sleeps"] += 1
            _sleep(d)
        return d


def _backoff_sleep(attempt: int, t_end: Optional[float]) -> None:
    d = random.uniform(0.0, min(BACKOFF_CAP_S,
                                BACKOFF_BASE_S * (2 ** attempt)))
    if t_end is not None:
        d = min(d, max(0.0, t_end - time.monotonic()))
    if d > 0.0:
        _STATS["sleeps"] += 1
        _sleep(d)


# ------------------------------------------------- epoch / version fencing
# per-server high-water mark of (epoch, version) from config responses
_SEEN: Dict[str, Tuple[Optional[int], int]] = {}
_SEEN_LOCK = threading.Lock()
_SEEN_LIMIT = 64  # distinct servers one process may talk to
_EPOCH_WARNED: set = set()


def note_config(url: str, epoch: Optional[int], version: int) -> None:
    """Record a config response's ``(epoch, version)`` and refuse
    regressions.

    Within one epoch the version counter is a fencing token and must be
    monotonic — a regression (reborn in-memory server, stale proxy)
    raises :class:`RPCStaleRead` so callers treat the read as an outage
    instead of fencing against the wrong counter.  An epoch CHANGE is
    the server declaring it lost state (WAL absent/torn): accepted, but
    warned once per transition.  Legacy servers that send no epoch
    (``epoch=None``) get the same regression check with ``None`` as the
    epoch — exactly the reborn-version-0 failure this exists to catch.
    """
    key = _netloc(url)
    with _SEEN_LOCK:
        prev = _SEEN.get(key)
        if prev is not None:
            pep, pv = prev
            if epoch == pep and version < pv:
                raise RPCStaleRead(
                    f"config server {key} answered version {version} "
                    f"after {pv} within epoch {epoch!r}: stale read "
                    f"refused (reborn server or stale cache)")
            if epoch != pep and (key, epoch) not in _EPOCH_WARNED:
                _EPOCH_WARNED.add((key, epoch))
                import sys
                print(f"kft: config server {key} changed epoch "
                      f"{pep!r} -> {epoch!r} (state loss or new "
                      f"incarnation); version counter restarts at "
                      f"{version} (was {pv})", file=sys.stderr)
                from ..trace import event as _trace_event
                _trace_event("rpc.epoch_change", category="rpc",
                             version=version,
                             attrs={"server": key, "old_epoch": pep,
                                    "new_epoch": epoch,
                                    "old_version": pv})
        if len(_SEEN) >= _SEEN_LIMIT and key not in _SEEN:
            _SEEN.pop(next(iter(_SEEN)))
        _SEEN[key] = (epoch, version)


def last_seen(url: str) -> Optional[Tuple[Optional[int], int]]:
    """The high-water ``(epoch, version)`` recorded for a server."""
    with _SEEN_LOCK:
        return _SEEN.get(_netloc(url))


def reset(url: Optional[str] = None) -> None:
    """Drop breaker/epoch/outage state (tests; a deliberately re-seeded
    deployment).  With ``url``, only that server's state."""
    if url is None:
        with _BREAKERS_LOCK:
            _BREAKERS.clear()
        with _SEEN_LOCK:
            _SEEN.clear()
            _EPOCH_WARNED.clear()
        with _OUTAGE_LOCK:
            _OUTAGES.clear()
        return
    key = _netloc(url)
    with _BREAKERS_LOCK:
        _BREAKERS.pop(key, None)
    with _SEEN_LOCK:
        _SEEN.pop(key, None)
    with _OUTAGE_LOCK:
        _OUTAGES.pop(key, None)


# ------------------------------------------------------- outage accounting
_OUTAGES: Dict[str, float] = {}  # netloc -> outage start (monotonic)
_OUTAGE_LOCK = threading.Lock()


def _note_outage(key: str) -> None:
    with _OUTAGE_LOCK:
        if key not in _OUTAGES:
            _OUTAGES[key] = time.monotonic()
            from ..trace import event as _trace_event
            _trace_event("rpc.outage", category="rpc",
                         attrs={"server": key, "phase": "begin"})


def _note_recovery(key: str) -> None:
    if not _OUTAGES:  # stays falsy until the first-ever outage
        return
    with _OUTAGE_LOCK:
        t0 = _OUTAGES.pop(key, None)
    if t0 is None:
        return
    dur = time.monotonic() - t0
    from ..monitor import get_monitor
    from ..trace import event as _trace_event
    get_monitor().set_gauge("kungfu_tpu_rpc_outage_seconds", dur,
                            labels={"server": key})
    _trace_event("rpc.outage", category="rpc", dur=dur,
                 attrs={"server": key, "phase": "end"})


def outage_age(url: str) -> Optional[float]:
    """Seconds the server has been failing, or None when healthy."""
    with _OUTAGE_LOCK:
        t0 = _OUTAGES.get(_netloc(url))
    return None if t0 is None else time.monotonic() - t0


_NET_ACCOUNT = None


def _account_bytes(key: str, out_bytes: int, in_bytes: int) -> None:
    """kfnet: request/response bytes per server, tagged control-plane
    (the target renders as ``ctrl:host:port`` so the bandwidth matrix
    and kfnet_report separate rpc overhead from state movement).  The
    import resolves once; afterwards the healthy path pays two counter
    adds — within the hot-path budget tests/test_kfguard.py pins."""
    global _NET_ACCOUNT
    if _NET_ACCOUNT is None:
        from ..monitor import net as _net
        _NET_ACCOUNT = _net.account
    if out_bytes:
        _NET_ACCOUNT("egress", out_bytes, peer=key, plane="control")
    _NET_ACCOUNT("ingress", in_bytes, peer=key, plane="control")


def _count_retry(key: str, exc: BaseException) -> None:
    _STATS["retries"] += 1
    kind = classify(exc)
    from ..monitor import get_monitor
    from ..trace import event as _trace_event
    get_monitor().inc("kungfu_tpu_rpc_retries_total",
                      labels={"server": key, "kind": kind})
    _trace_event("rpc.retry", category="rpc",
                 attrs={"server": key, "kind": kind})


# -------------------------------------------------------------------- call
def call(url: str, *, method: str = "GET", body: Optional[bytes] = None,
         headers: Optional[Dict[str, str]] = None,
         attempt_timeout: float = 5.0, deadline: Optional[float] = None,
         retry_unseeded: bool = False,
         check: Optional[Callable[[bytes], object]] = None):
    """One control-plane HTTP call under the unified retry policy.

    ``deadline=None`` performs exactly ONE attempt (poll loops bring
    their own cadence); a float is the overall time budget across
    attempts, each bounded by ``attempt_timeout``, with jittered
    exponential backoff in between.  ``check(body) -> result`` runs per
    attempt; a ``ValueError``/``KeyError``/:class:`RPCStaleRead` it
    raises marks the attempt bad-response (retryable) — the parsed
    result is what ``call`` returns.  404 responses are terminal unless
    ``retry_unseeded`` (a booting bootstrap tolerates "no config yet").
    Terminal failures re-raise the LAST underlying error, never a
    synthetic one."""
    t_end = (None if deadline is None
             else time.monotonic() + deadline)
    br = _breaker_for(url)
    key = _netloc(url)
    attempt = 0
    while True:
        if not br.allow():
            last: BaseException = RPCCircuitOpen(
                f"circuit open for {key}: {br._fails} consecutive "
                f"failures, next probe in {br.probe_eta():.2f}s")
        else:
            from ..chaos import point as _chaos_point
            _chaos_point("rpc.attempt")
            _STATS["requests"] += 1
            req = urllib.request.Request(url, data=body, method=method)
            for k, v in (headers or {}).items():
                req.add_header(k, v)
            try:
                with _urlopen(req, timeout=attempt_timeout) as r:
                    raw = r.read()
                _account_bytes(key, len(body) if body else 0, len(raw))
                out = raw if check is None else check(raw)
            except urllib.error.HTTPError as e:
                # an HTTP status is an ANSWER: the server is alive
                code = e.code
                if code < 500 and not (code == 404 and retry_unseeded):
                    br.success()
                    _note_recovery(key)
                    raise
                if code < 500:
                    br.success()  # 404-unseeded, retried below
                else:
                    br.failure()
                    _note_outage(key)
                last = e
            except RPCStaleRead as e:
                br.success()  # transport fine; the CONTENT is refused
                last = e
            except (ValueError, KeyError) as e:
                br.success()  # bad-response: torn JSON from a live server
                last = e
            except (urllib.error.URLError, OSError) as e:
                br.failure()
                _note_outage(key)
                last = e
            else:
                br.success()
                _note_recovery(key)
                return out
        if t_end is None or time.monotonic() >= t_end:
            raise last
        _count_retry(key, last)
        _backoff_sleep(attempt, t_end)
        attempt += 1


def stats() -> Dict[str, int]:
    """Copy of the module counters (requests / retries / sleeps) for the
    hot-path micro-asserts."""
    return dict(_STATS)
