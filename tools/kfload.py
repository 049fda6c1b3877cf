#!/usr/bin/env python
"""kfload: traffic generator + SLO bench harness for the serving path.

Drives a live serving server (yours via ``--url``, or a tiny
seed-initialized one it spawns itself) with one of three generators and
writes ``SERVING_BENCH.json`` — client-side p50/p99 TTFT / TPOT / e2e
per offered-load rung, goodput against the configured SLOs, and the
saturation knee:

* **sweep** (default): open-loop Poisson arrivals at each rate in
  ``--rates`` — the right model for capacity questions, because a slow
  server does NOT slow the offered load down (closed-loop generators
  flatter a saturated server by self-throttling).
* **closed**: ``--concurrency`` workers in a closed loop — the right
  model for "N agents hammering as fast as answers come back".
* **replay**: re-offer a recorded ``kfrequests.*.jsonl`` request
  journal (``--trace``, written by the server under ``KFT_TRACE_DIR``)
  with its real arrival spacing and request sizes, optionally
  time-scaled by ``--speed`` — production traffic as the benchmark.

Prompts draw from a shared-prefix mix (``--prefix-frac`` of requests
share one prompt prefix) so prefix-cache-enabled servers see realistic
reuse.  TTFT is measured CLIENT-side off the streaming response
(``stream=true`` chunked ndjson) — the number a user actually
experiences, queue and wire included; the server's own journal
(``/requests``) holds the server-side decomposition of the same
requests.

SLO targets come from the same ``KFT_SLO_*`` knobs the server reads
(docs/knobs.md): a request is "good" when every configured objective
is met, and goodput is good requests per second.  The saturation knee
is the highest swept rate whose goodput still covers >= 90% of offered
load.

``--smoke`` (wired into tools/ci.sh and ``make load-smoke``) spawns a
tiny CPU server, runs a 3-rung sweep, and asserts the whole
observability loop: bench shape, SLO gauges on /metrics, /requests
journal shape, and a kftrace+kfrequests Chrome-trace merge round-trip.

    python tools/kfload.py --url http://host:8100 --rates 2,8,32
    python tools/kfload.py --mode replay --trace kfrequests.123.jsonl
    python tools/kfload.py --smoke
"""
from __future__ import annotations

import argparse
import http.client
import json
import os
import random
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from kungfu_tpu.utils import knobs  # noqa: E402

# tiny spawned server (same footprint the serving chaos tier uses):
# real engine, negligible CPU cost per token
_SERVER_ARGS = ["--vocab", "256", "--d-model", "32", "--n-heads", "2",
                "--n-layers", "2", "--d-ff", "64", "--max-seq", "128",
                "--slots", "4", "--block", "16", "--blocks", "64",
                "--chunk", "4", "--buckets", "16", "--prefix-cache",
                "--dtype", "float32"]
_READY_S = 180.0


# ------------------------------------------------------------ client
def _request_once(url: str, prompt: List[int], max_new: int,
                  timeout: float) -> Dict[str, object]:
    """One streamed /generate call, timed client-side.  TTFT = first
    token chunk on the wire; TPOT = the per-token slope after it."""
    t0 = time.perf_counter()
    body = json.dumps({"prompt": prompt, "max_new": max_new,
                       "temperature": 0.0, "stream": True}).encode()
    req = urllib.request.Request(
        url + "/generate", data=body,
        headers={"Content-Type": "application/json"})
    ttft = None
    tokens = 0
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            for line in r:           # http.client decodes the chunking
                line = line.strip()
                if not line:
                    continue
                rec = json.loads(line)
                if rec.get("error"):
                    return {"ok": False, "error": str(rec["error"])}
                if rec.get("done"):
                    break
                got = len(rec.get("tokens") or ())
                if got and ttft is None:
                    ttft = time.perf_counter() - t0
                tokens += got
    except (OSError, ValueError,
            http.client.HTTPException) as e:
        return {"ok": False, "error": type(e).__name__}
    e2e = time.perf_counter() - t0
    if ttft is None or tokens == 0:
        return {"ok": False, "error": "no tokens streamed"}
    return {"ok": True, "ttft_ms": ttft * 1e3, "e2e_ms": e2e * 1e3,
            "tpot_ms": ((e2e - ttft) / (tokens - 1) * 1e3
                        if tokens > 1 else 0.0),
            "tokens": tokens}


def _make_prompt(rng: random.Random, length: int, vocab: int,
                 prefix: Optional[List[int]], prefix_frac: float
                 ) -> List[int]:
    if prefix and rng.random() < prefix_frac:
        tail = [rng.randrange(1, vocab) for _ in
                range(max(0, length - len(prefix)))]
        return (prefix + tail)[:length]
    return [rng.randrange(1, vocab) for _ in range(length)]


# ------------------------------------------------------- generators
def _run_arrivals(urls, offsets: List[float],
                  prompts: List[List[int]], max_news: List[int],
                  timeout: float):
    """Open-loop core: fire request i at ``offsets[i]`` seconds after
    start, on its own thread, regardless of how the server is doing.

    ``urls`` is one base URL or a fleet of them: request i goes to
    ``urls[i % len(urls)]`` — a DETERMINISTIC round-robin stand-in for
    a front-end dispatcher (each replica sees the same offered share,
    which is exactly the balanced-front-end premise the kffleet
    ``imbalance`` detector diagnoses against), NOT a load-aware
    router."""
    if isinstance(urls, str):
        urls = [urls]
    results: List[Optional[Dict[str, object]]] = [None] * len(offsets)

    def one(i: int) -> None:
        r = _request_once(urls[i % len(urls)], prompts[i], max_news[i],
                          timeout)
        r["replica"] = i % len(urls)
        results[i] = r

    t0 = time.perf_counter()
    threads = []
    for i, off in enumerate(offsets):
        lag = t0 + off - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        t = threading.Thread(target=one, args=(i,), daemon=True)
        t.start()
        threads.append(t)
    for t in threads:
        t.join(timeout=timeout + 5.0)
    span = time.perf_counter() - t0
    return [r if r is not None else
            {"ok": False, "error": "timed out"} for r in results], span


def _poisson_offsets(rng: random.Random, rate: float,
                     duration: float) -> List[float]:
    offs, t = [], 0.0
    while True:
        t += rng.expovariate(rate)
        if t >= duration:
            return offs or [0.0]
        offs.append(t)


def _synth_trace(spec: str, duration: float):
    """``--trace synth:diurnal:<seed>[:k=v,...]`` — a seeded synthetic
    diurnal/bursty schedule instead of a recorded journal, same
    ``(offsets, prompt_lens, output_budgets)`` contract.  The generator
    (kungfu_tpu.sim.serving.synth_diurnal_schedule) is a pure function
    of its arguments: two runs with the same spec are bit-identical.
    Optional keys: ``base``/``peak`` (rps), ``spike`` (rps, square
    burst over the 40-65% window), ``plen``/``new`` (tokens)."""
    from kungfu_tpu.sim.serving import synth_diurnal_schedule
    parts = spec.split(":")
    if len(parts) < 3 or parts[0] != "synth" or parts[1] != "diurnal":
        raise SystemExit(
            f"kfload: bad synthetic trace spec {spec!r} "
            f"(want synth:diurnal:<seed>[:k=v,...])")
    try:
        seed = int(parts[2])
    except ValueError:
        raise SystemExit(f"kfload: non-integer seed in {spec!r}")
    kw = {"base_rps": 2.0, "peak_rps": 8.0, "spike_rps": 0.0,
          "prompt_len": 8, "max_new": 8}
    keymap = {"base": "base_rps", "peak": "peak_rps",
              "spike": "spike_rps", "plen": "prompt_len",
              "new": "max_new"}
    for kv in ",".join(parts[3:]).split(","):
        if not kv:
            continue
        k, _, v = kv.partition("=")
        if k not in keymap or not v:
            raise SystemExit(f"kfload: bad synth key {kv!r} in {spec!r} "
                             f"(known: {sorted(keymap)})")
        try:
            kw[keymap[k]] = (int(v) if keymap[k] in
                             ("prompt_len", "max_new") else float(v))
        except ValueError:
            raise SystemExit(f"kfload: bad synth value {kv!r}")
    return synth_diurnal_schedule(seed, duration_s=duration, **kw)


def _load_journal(path: str):
    """(relative arrival offsets, prompt lengths, output budgets) from
    a kfrequests journal (finished records only)."""
    recs = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue   # torn tail write, same as the trace merger
            if rec.get("kind") == "anchor":
                continue
            if rec.get("arrival_t") is not None:
                recs.append(rec)
    if not recs:
        raise SystemExit(f"kfload: no request records in {path}")
    recs.sort(key=lambda r: r["arrival_t"])
    base = recs[0]["arrival_t"]
    offs = [r["arrival_t"] - base for r in recs]
    plens = [max(1, int(r.get("prompt_tokens") or 1)) for r in recs]
    outs = [max(1, int(r.get("output_tokens") or 1)) for r in recs]
    return offs, plens, outs


# ------------------------------------------------------------ stats
def _pctl(vals: List[float], q: float) -> float:
    s = sorted(vals)
    if not s:
        return 0.0
    i = min(len(s) - 1, max(0, int(round(q * (len(s) - 1)))))
    return s[i]


def _rung_stats(tag: str, offered_rps: Optional[float],
                results: List[Dict[str, object]], span: float,
                slos) -> Dict[str, object]:
    ok = [r for r in results if r.get("ok")]
    out: Dict[str, object] = {
        "rung": tag, "offered_rps": offered_rps,
        "requests": len(results), "completed": len(ok),
        "errors": len(results) - len(ok),
        "span_s": round(span, 3),
        "achieved_rps": round(len(ok) / span, 3) if span else 0.0,
    }
    for obj in ("ttft", "tpot", "e2e"):
        vals = [r[f"{obj}_ms"] for r in ok]
        out[f"{obj}_p50_ms"] = round(_pctl(vals, 0.50), 2)
        out[f"{obj}_p99_ms"] = round(_pctl(vals, 0.99), 2)
    good = [r for r in ok
            if all(r[f"{s.objective}_ms"] <= s.target_ms
                   for s in slos)]
    out["good"] = len(good)
    out["goodput_rps"] = (round(len(good) / span, 3) if span
                          else 0.0)
    out["goodput_frac"] = (round(len(good) / len(results), 4)
                           if results else 0.0)
    replicas = sorted({r.get("replica") for r in results
                       if r.get("replica") is not None})
    if len(replicas) > 1:
        # fleet fan-out: the per-replica split of the same rung, so
        # the committed bench shows who absorbed what
        by_rep = {}
        for idx in replicas:
            rs = [r for r in results if r.get("replica") == idx]
            rok = [r for r in rs if r.get("ok")]
            by_rep[str(idx)] = {
                "requests": len(rs), "completed": len(rok),
                "ttft_p50_ms": round(
                    _pctl([r["ttft_ms"] for r in rok], 0.50), 2),
                "ttft_p99_ms": round(
                    _pctl([r["ttft_ms"] for r in rok], 0.99), 2),
            }
        out["by_replica"] = by_rep
    return out


def _find_knee(rungs: List[Dict[str, object]]) -> Optional[float]:
    """Highest swept offered rate whose goodput still covers >= 90% of
    the offered load — past it, added demand turns into queueing, not
    good answers."""
    knee = None
    for r in rungs:
        off = r.get("offered_rps")
        if off and r["goodput_rps"] >= 0.9 * off:
            knee = max(knee or 0.0, off)
    return knee


# ----------------------------------------------------- server spawn
def _free_port() -> int:
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_server(trace_dir: str, log_path: str):
    port = _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               KFT_TRACE_DIR=trace_dir)
    log = open(log_path, "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "kungfu_tpu.serving",
         "--port", str(port)] + _SERVER_ARGS,
        env=env, stdout=log, stderr=subprocess.STDOUT)
    url = f"http://127.0.0.1:{port}"
    deadline = time.monotonic() + _READY_S
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            log.close()
            raise SystemExit(f"kfload: spawned server died "
                             f"(rc={proc.returncode}, see {log_path})")
        try:
            with urllib.request.urlopen(url + "/stats",
                                        timeout=2.0) as r:
                if r.status == 200:
                    return proc, url, log
        except (OSError, urllib.error.URLError):
            pass
        time.sleep(0.25)
    proc.kill()
    log.close()
    raise SystemExit("kfload: spawned server never became ready")


def _stop_server(proc, log) -> None:
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    log.close()


# ------------------------------------------------------- fleet bench
# service-time shape for the spawned sim replicas: slow enough that
# one replica's knee sits INSIDE the swept rates (2 slots x ~200ms
# per request ≈ 10 rps capacity), so the single-vs-fleet knee ratio
# is a measurement, not a ceiling artifact
_SIM_REPLICA_ENV = {"KFT_SIM_LITE": "1", "KFT_SIM_SERVE_SLOTS": "2",
                    "KFT_SIM_SERVE_PREFILL_MS": "1.0",
                    "KFT_SIM_SERVE_DECODE_MS": "25.0"}


def _spawn_sim_replica(log_path: str):
    """One standalone kfsim serving replica (sim/serving.py): the
    production HTTP contract over a deterministic synthetic service
    model, jax-free under KFT_SIM_LITE — what makes the fleet bench
    runnable data-plane-free on any box."""
    port = _free_port()
    env = dict(os.environ, **_SIM_REPLICA_ENV)
    log = open(log_path, "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "kungfu_tpu.sim.serving",
         "--port", str(port)],
        env=env, stdout=log, stderr=subprocess.STDOUT)
    url = f"http://127.0.0.1:{port}"
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            log.close()
            raise SystemExit(f"kfload: sim replica died "
                             f"(rc={proc.returncode}, see {log_path})")
        try:
            with urllib.request.urlopen(url + "/stats",
                                        timeout=2.0) as r:
                if r.status == 200:
                    return proc, url, log
        except (OSError, urllib.error.URLError):
            pass
        time.sleep(0.1)
    proc.kill()
    log.close()
    raise SystemExit("kfload: sim replica never became ready")


def _fleet_bench(args) -> int:
    """``--fleet-bench N``: spawn N sim serving replicas, sweep ONE
    replica, then sweep the round-robin fleet of all N, and commit
    both knees + their ratio to ``FLEET_SERVING_BENCH.json`` — the
    scaling headroom a front-end dispatcher buys, measured with the
    same open-loop generator both times."""
    n = args.fleet_bench
    # tight TTFT budget so the single replica's knee is a sharp
    # queueing cliff inside the swept rates (the default 2s budget
    # absorbs seconds of queue and blurs the knee); setdefault so an
    # operator's own KFT_SLO_* wins
    for k, v in (("KFT_SLO_TTFT_MS", "250"),
                 ("KFT_SLO_TPOT_MS", "100"),
                 ("KFT_SLO_E2E_MS", "2000")):
        os.environ.setdefault(k, v)
    out_dir = tempfile.mkdtemp(prefix="kfload-fleet-")
    fleet = [_spawn_sim_replica(os.path.join(out_dir, f"rep{i}.log"))
             for i in range(n)]
    urls = [u for _p, u, _l in fleet]
    try:
        args.fleet = None
        args.url = urls[0]
        single = run_bench(args)
        args.fleet = urls
        fleet_doc = run_bench(args)
    finally:
        for proc, _u, log in fleet:
            _stop_server(proc, log)
    k1 = single["saturation_knee_rps"]
    kn = fleet_doc["saturation_knee_rps"]
    doc = {
        "bench": "kfload-fleet",
        "replicas": n,
        "seed": args.seed,
        "rates": args.rates,
        "duration_s": args.duration,
        "sim_replica_env": dict(_SIM_REPLICA_ENV),
        "slo": {obj: os.environ.get(f"KFT_SLO_{obj.upper()}_MS")
                for obj in ("ttft", "tpot", "e2e")},
        "single": {"url": single["url"], "rungs": single["rungs"],
                   "saturation_knee_rps": k1},
        "fleet": {"urls": urls, "rungs": fleet_doc["rungs"],
                  "saturation_knee_rps": kn},
        "knee_ratio": (round(kn / k1, 3)
                       if k1 and kn is not None else None),
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"kfload: fleet bench -> {args.out} (single knee {k1} rps, "
          f"{n}-replica fleet knee {kn} rps, "
          f"ratio {doc['knee_ratio']})")
    return 0


# -------------------------------------------------------------- main
def run_bench(args) -> Dict[str, object]:
    from kungfu_tpu.serving.slo import load_slos
    rng = random.Random(args.seed)
    slos = load_slos()
    timeout = knobs.get("KFT_LOAD_TIMEOUT_S")
    urls = [u.rstrip("/") for u in
            (args.fleet if getattr(args, "fleet", None)
             else [args.url])]
    url = urls[0]
    prefix = [rng.randrange(1, args.vocab)
              for _ in range(max(1, args.prompt_len // 2))]

    def prompts_for(n: int, plens: Optional[List[int]] = None):
        plens = plens or [args.prompt_len] * n
        return [_make_prompt(rng, plens[i], args.vocab, prefix,
                             args.prefix_frac) for i in range(n)]

    rungs: List[Dict[str, object]] = []
    if args.mode == "sweep":
        # warm-up absorbs the jit compiles so rung 1 is steady-state
        # (every fleet member gets one)
        for u in urls:
            for p in prompts_for(2):
                _request_once(u, p, args.max_new, timeout)
        for rate in args.rates:
            offs = _poisson_offsets(rng, rate, args.duration)
            ps = prompts_for(len(offs))
            res, span = _run_arrivals(
                urls, offs, ps, [args.max_new] * len(offs), timeout)
            # the rung is judged against what this Poisson draw
            # actually offered, not the nominal rate — a short draw
            # must not fail the knee test for load it never sent
            realized = round(len(offs) / args.duration, 3)
            rungs.append(_rung_stats(f"poisson-{rate:g}rps", realized,
                                     res, span, slos))
            print(f"kfload: {rungs[-1]['rung']}: "
                  f"{rungs[-1]['completed']}/{rungs[-1]['requests']} "
                  f"ok, ttft p99 {rungs[-1]['ttft_p99_ms']}ms, "
                  f"goodput {rungs[-1]['goodput_rps']}rps",
                  flush=True)
    elif args.mode == "closed":
        for p in prompts_for(2):
            _request_once(url, p, args.max_new, timeout)
        results: List[Dict[str, object]] = []
        res_lock = threading.Lock()
        quota = [args.requests]
        t0 = time.perf_counter()

        def worker() -> None:
            while True:
                with res_lock:
                    if quota[0] <= 0:
                        return
                    quota[0] -= 1
                p = _make_prompt(rng, args.prompt_len, args.vocab,
                                 prefix, args.prefix_frac)
                r = _request_once(url, p, args.max_new, timeout)
                with res_lock:
                    results.append(r)

        ts = [threading.Thread(target=worker, daemon=True)
              for _ in range(args.concurrency)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=timeout * args.requests)
        span = time.perf_counter() - t0
        rungs.append(_rung_stats(
            f"closed-c{args.concurrency}", None, results, span, slos))
    else:   # replay
        if str(args.trace).startswith("synth:"):
            offs, plens, outs = _synth_trace(args.trace, args.duration)
        else:
            offs, plens, outs = _load_journal(args.trace)
        offs = [o / args.speed for o in offs]
        ps = prompts_for(len(offs), plens)
        res, span = _run_arrivals(urls, offs, ps, outs, timeout)
        offered = len(offs) / max(offs[-1], 1e-9) if offs else None
        rungs.append(_rung_stats(
            f"replay-x{args.speed:g}", round(offered, 3), res, span,
            slos))

    return {
        "bench": "kfload",
        "mode": args.mode,
        "url": url,
        "fleet": urls if len(urls) > 1 else None,
        "prompt_len": args.prompt_len,
        "max_new": args.max_new,
        "prefix_frac": args.prefix_frac,
        "seed": args.seed,
        "slo": {s.objective: {"target_ms": s.target_ms,
                              "percentile": s.percentile}
                for s in slos},
        "rungs": rungs,
        "saturation_knee_rps": _find_knee(rungs),
    }


def _smoke() -> int:
    """Spawn a tiny server, sweep 3 rungs, assert the whole loop."""
    trace_dir = tempfile.mkdtemp(prefix="kfload-smoke-")
    proc, url, log = _spawn_server(
        trace_dir, os.path.join(trace_dir, "server.log"))
    try:
        args = _parse([
            "--url", url, "--rates", "2,4,8", "--duration", "2",
            "--out", os.path.join(trace_dir, "SERVING_BENCH.json")])
        doc = run_bench(args)
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=2)
        assert len(doc["rungs"]) >= 3, doc
        for r in doc["rungs"]:
            assert r["completed"] > 0, r
            assert r["ttft_p99_ms"] > 0 and r["e2e_p50_ms"] > 0, r
        # the server side of the same requests: SLO gauges + journal
        with urllib.request.urlopen(url + "/metrics",
                                    timeout=5.0) as r:
            metrics = r.read().decode()
        assert "kungfu_tpu_slo_compliance" in metrics, metrics[:400]
        assert "kungfu_tpu_slo_budget_burn" in metrics
        with urllib.request.urlopen(url + "/requests?n=8",
                                    timeout=5.0) as r:
            snap = json.load(r)
        assert snap["finished"] and "slo" in snap, snap
        assert snap["finished"][-1]["uid"] is not None
    finally:
        _stop_server(proc, log)
    # merge round-trip: the journal the server just wrote renders as
    # nested request spans next to the engine's kftrace stream
    from kungfu_tpu.trace.merge import (discover, discover_requests,
                                        merge)
    req_paths = discover_requests([trace_dir])
    assert req_paths, f"no kfrequests journal under {trace_dir}"
    trace = merge(discover([trace_dir]), request_paths=req_paths)
    names = {e["name"] for e in trace["traceEvents"]}
    assert any(n.startswith("req ") for n in names), sorted(names)[:20]
    assert {"queue", "prefill", "decode"} <= names, sorted(names)[:20]
    print(f"kfload smoke: OK ({len(doc['rungs'])} rungs, "
          f"{sum(r['completed'] for r in doc['rungs'])} requests, "
          f"bench -> {args.out})")
    return 0


def _parse(argv):
    ap = argparse.ArgumentParser(
        prog="kfload", description=__doc__.split("\n")[0])
    ap.add_argument("--url", default=None,
                    help="serving server base URL (default: spawn a "
                         "tiny seed-initialized CPU server)")
    ap.add_argument("--fleet", nargs="+", default=None, metavar="URL",
                    help="fan requests out round-robin over several "
                         "serving replicas (deterministic stand-in "
                         "dispatcher, not a load-aware router)")
    ap.add_argument("--fleet-bench", type=int, default=0, metavar="N",
                    help="spawn N sim serving replicas, sweep one vs "
                         "the fleet, write FLEET_SERVING_BENCH.json")
    ap.add_argument("--mode", choices=("sweep", "closed", "replay"),
                    default="sweep")
    ap.add_argument("--rates", default="2,4,8",
                    help="sweep mode: comma-separated offered rates "
                         "(requests/s), one rung each")
    ap.add_argument("--duration", type=float, default=10.0,
                    help="sweep mode: seconds per rung")
    ap.add_argument("--concurrency", type=int, default=4,
                    help="closed mode: worker count")
    ap.add_argument("--requests", type=int, default=64,
                    help="closed mode: total requests")
    ap.add_argument("--trace", default=None,
                    help="replay mode: a kfrequests.*.jsonl journal")
    ap.add_argument("--speed", type=float, default=1.0,
                    help="replay mode: time-compression factor")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=256,
                    help="token id range for generated prompts (match "
                         "your server's vocab)")
    ap.add_argument("--prefix-frac", type=float, default=0.5,
                    help="fraction of prompts sharing one prefix")
    ap.add_argument("--seed", type=int,
                    default=knobs.get("KFT_LOAD_SEED"))
    ap.add_argument("--out", default="SERVING_BENCH.json")
    ap.add_argument("--smoke", action="store_true",
                    help="spawn-sweep-assert self-test (CI step)")
    args = ap.parse_args(argv)
    args.rates = [float(r) for r in str(args.rates).split(",") if r]
    if args.mode == "replay" and not args.smoke and not args.trace:
        ap.error("--mode replay requires --trace")
    if args.fleet_bench:
        if args.fleet_bench < 2:
            ap.error("--fleet-bench needs N >= 2 replicas")
        if args.out == "SERVING_BENCH.json":
            args.out = "FLEET_SERVING_BENCH.json"
    return args


def main(argv=None) -> int:
    args = _parse(argv)
    if args.smoke:
        return _smoke()
    if args.fleet_bench:
        return _fleet_bench(args)
    proc = log = None
    if args.url is None and not args.fleet:
        trace_dir = tempfile.mkdtemp(prefix="kfload-")
        proc, args.url, log = _spawn_server(
            trace_dir, os.path.join(trace_dir, "server.log"))
        print(f"kfload: spawned tiny server at {args.url} "
              f"(journal + traces under {trace_dir})", flush=True)
    try:
        doc = run_bench(args)
    finally:
        if proc is not None:
            _stop_server(proc, log)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
    knee = doc["saturation_knee_rps"]
    print(f"kfload: {len(doc['rungs'])} rung(s) -> {args.out} "
          f"(saturation knee: "
          f"{knee if knee is not None else 'not reached'})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
