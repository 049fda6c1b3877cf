"""HTTP front-end for the continuous-batching engine.

The missing piece between "an engine you can call with a batch" and "a
service you can send requests to": a stdlib-only HTTP server whose
handlers enqueue requests and a single scheduler thread that owns the
engine — requests arriving at different times join the SAME decode
batch (continuous batching across the wire), finished sequences leave
it, and callers block only on their own completion.

    from kungfu_tpu.serving import DecodeEngine, ServingServer
    srv = ServingServer(engine, port=8100).start()
    # POST /generate  {"prompt": [1,2,3], "max_new": 16,
    #                  "temperature": 0.8, "eos": 50256}
    #   -> {"uid": N, "tokens": [...]}
    # GET  /stats -> engine stats + queue depth
    srv.close()

Design notes: the engine is single-threaded by construction (device
state, block tables); the scheduler thread is its sole owner, and
handlers hand it work through a submission list + per-uid events, never
touching engine *mutating* state.  /stats reads the pure-Python stat
counters directly — a GIL-consistent monitoring snapshot that may be
torn across fields, which is fine for metrics and the one documented
exception to the ownership rule.  A scheduler death (device error) or
close() releases every waiting client with a 5xx instead of a wedge.
Built on the shared BackgroundHTTPServer lifecycle (same helper as the
config server and /metrics; the reference runs its config server the
same way).
"""
import json
import queue
import threading
import time
from http.server import BaseHTTPRequestHandler
from typing import Dict, List, Optional

from ..utils.http import BackgroundHTTPServer
from .engine import DecodeEngine, Request

_STREAM_END = object()


class ServingServer:
    """Wrap a :class:`DecodeEngine` in an HTTP service.

    ``start()`` spawns the HTTP listener and the scheduler thread;
    ``close()`` drains both (releasing any waiting clients with 503).
    Single-host serving — the training side's launcher/elastic machinery
    is a separate concern.
    """

    def __init__(self, engine: DecodeEngine, host: str = "127.0.0.1",
                 port: int = 0):
        self.engine = engine
        self._lock = threading.Lock()        # submissions + results
        self._pending: List[Request] = []
        self._done: Dict[int, List[int]] = {}
        self._events: Dict[int, threading.Event] = {}
        self._streams: Dict[int, "queue.Queue"] = {}
        self._next_uid = 1
        # scheduler-thread-only callback: fan tokens out to stream
        # queues, CHAINING any callback the caller already installed on
        # the engine (overwriting it silently would eat their events)
        self._chained_on_tokens = engine.on_tokens
        engine.on_tokens = self._on_tokens
        self._fatal: Optional[str] = None
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._sched: Optional[threading.Thread] = None
        self._http = BackgroundHTTPServer(self._handler_factory, host,
                                          port)
        self.host, self.port = self._http.host, self._http.port

    def _handler_factory(self, _srv):
        server = self

        class Handler(BaseHTTPRequestHandler):
            # chunked transfer is an HTTP/1.1 construct: a 1.0 status
            # line makes compliant clients skip chunk decoding and read
            # raw chunk framing as body.  Non-stream replies all send
            # Content-Length, so keep-alive stays correct.
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):            # quiet
                pass

            def _reply(self, code, obj):
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/stats":
                    with server._lock:
                        depth = len(server._pending)
                    s = dict(server.engine.stats.summary(),
                             pending=depth,
                             busy=server.engine.busy)
                    self._reply(200, s)
                elif self.path.startswith("/metrics"):
                    # Prometheus exposition: queue-wait / prefill /
                    # per-token decode summaries + prefix-cache gauges
                    # the engine feeds (docs/monitoring.md)
                    from ..monitor import get_monitor
                    body = get_monitor().render_metrics().encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "text/plain")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                elif self.path.startswith("/requests"):
                    # live request journal: open + recently finished
                    # lifecycles and the current SLO evaluation
                    # (serving/slo.py; docs/serving.md).  ?n= caps the
                    # finished tail.
                    from urllib.parse import parse_qs, urlsplit
                    qs = parse_qs(urlsplit(self.path).query)
                    try:
                        n = int(qs.get("n", ["64"])[0])
                    except ValueError:
                        n = 64
                    self._reply(200, server.engine.journal.snapshot(n))
                else:
                    self._reply(404, {"error": "unknown path"})

            def do_POST(self):
                if self.path != "/generate":
                    self._reply(404, {"error": "unknown path"})
                    return
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    req = json.loads(self.rfile.read(n))
                    prompt = [int(t) for t in req["prompt"]]
                    max_new = int(req["max_new"])
                    eos = req.get("eos")
                    eos = None if eos is None else int(eos)
                    temp = float(req.get("temperature", 0.0))
                except (KeyError, TypeError, ValueError) as e:
                    self._reply(400, {"error": f"bad request: {e}"})
                    return
                stream = bool(req.get("stream", False))
                try:
                    uid, ev = server._submit(prompt, max_new, eos, temp,
                                             stream=stream)
                except ValueError as e:
                    self._reply(422, {"error": str(e)})
                    return
                except RuntimeError as e:         # already closed/dead
                    self._reply(503, {"error": str(e)})
                    return
                if stream:
                    self._stream_reply(uid)
                    return
                ev.wait()
                with server._lock:
                    tokens = server._done.pop(uid, None)
                    server._events.pop(uid, None)
                    fatal = server._fatal
                if tokens is None:
                    self._reply(503, {"error": fatal or
                                      "server closed before completion"})
                else:
                    self._reply(200, {"uid": uid, "tokens": tokens})

            def _chunk(self, payload: bytes):
                self.wfile.write(f"{len(payload):x}\r\n".encode()
                                 + payload + b"\r\n")

            def _stream_reply(self, uid):
                """Chunked transfer: one JSON line per token batch as
                the engine produces it, then a final done line.  Thanks
                to deterministic replay + the engine's emitted-count
                suppression, the stream never duplicates or rolls back
                tokens across preemptions."""
                q = server._streams[uid]
                total = 0
                try:
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "application/x-ndjson")
                    self.send_header("Transfer-Encoding", "chunked")
                    self.end_headers()
                    while True:
                        item = q.get()
                        if item is _STREAM_END:
                            break
                        total += len(item)
                        self._chunk(json.dumps(
                            {"uid": uid,
                             "tokens": item}).encode() + b"\n")
                finally:
                    # a client disconnect raises out of the writes above;
                    # the uid's queue/event/result must not leak (the
                    # scheduler would keep feeding an orphaned queue)
                    with server._lock:
                        done = uid in server._done
                        server._done.pop(uid, None)
                        server._streams.pop(uid, None)
                        server._events.pop(uid, None)
                        fatal = server._fatal
                tail = ({"uid": uid, "done": True, "tokens_total": total}
                        if done else
                        {"uid": uid, "error": fatal or "server closed"})
                self._chunk(json.dumps(tail).encode() + b"\n")
                self.wfile.write(b"0\r\n\r\n")

        return Handler

    def _on_tokens(self, uid, new_tokens):
        """Runs on the scheduler thread (engine callback)."""
        if self._chained_on_tokens is not None:
            self._chained_on_tokens(uid, new_tokens)
        q = self._streams.get(uid)
        if q is not None:
            q.put(list(new_tokens))

    # ------------------------------------------------------------ plumbing
    def _submit(self, prompt, max_new, eos, temperature, stream=False):
        with self._lock:
            if self._stop.is_set() or self._fatal:
                raise RuntimeError(self._fatal or "server is closed")
            uid = self._next_uid
            self._next_uid += 1
            req = Request(uid=uid, prompt=prompt, max_new=max_new,
                          eos=eos, temperature=temperature,
                          arrival_t=time.perf_counter())
            # validate NOW so the caller gets a 422, not a wedged wait
            # (shape checks only — stateless, so no race with the
            # scheduler thread that owns the engine)
            self.engine.validate_shape(req)
            self._pending.append(req)
            ev = threading.Event()
            self._events[uid] = ev
            if stream:
                self._streams[uid] = queue.Queue()
        self._wake.set()
        return uid, ev

    def _release_all_waiters(self) -> None:
        with self._lock:
            evs = list(self._events.values())
            qs = list(self._streams.values())
        for ev in evs:
            ev.set()
        for q in qs:
            q.put(_STREAM_END)

    def _scheduler(self):
        """Sole owner of the engine after start().  Any engine exception
        (device error, runtime failure) is fatal: record it and release
        every waiting client with an error instead of a silent wedge."""
        try:
            while not self._stop.is_set():
                with self._lock:
                    new, self._pending = self._pending, []
                for r in new:
                    self.engine.submit(r)
                progressed = (self.engine.step() if self.engine.busy
                              else False)
                finished = self.engine.take_results()
                if finished:
                    with self._lock:
                        self._done.update(finished)
                        evs = [self._events[u] for u in finished
                               if u in self._events]
                        qs = [self._streams[u] for u in finished
                              if u in self._streams]
                    for ev in evs:
                        ev.set()
                    for q in qs:
                        q.put(_STREAM_END)
                if not progressed and not self.engine.busy:
                    self._wake.wait(timeout=0.25)  # idle: park
                    self._wake.clear()
                else:
                    time.sleep(0)                  # yield to HTTP threads
        except Exception as e:  # noqa: BLE001 — anything is fatal here
            with self._lock:
                self._fatal = f"engine failed: {type(e).__name__}: {e}"
        finally:
            self._release_all_waiters()

    # -------------------------------------------------------------- public
    def start(self) -> "ServingServer":
        self._sched = threading.Thread(target=self._scheduler,
                                       daemon=True)
        self._sched.start()
        self._http.start()
        return self

    def close(self) -> None:
        self._stop.set()
        self._wake.set()
        if self._sched:
            self._sched.join(timeout=30)   # releases waiters on exit
        self._http.stop()
        # requests still in flight never finish: close their journal
        # records as evicted (terminal serving.evict span) so the ring
        # and the kfrequests stream don't end with dangling lifecycles
        self.engine.journal.evict_open("server-closed")
