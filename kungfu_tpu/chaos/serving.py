"""kfchaos serving tier: prove the SLO plane against a LIVE server.

``tier="serving"`` scenarios spawn one real CPU serving process
(``python -m kungfu_tpu.serving``, tiny seed-initialized model) with the
fault plan armed through ``KFT_CHAOS_PLAN`` — chaos arming is
import-time, so the server must be a fresh process, exactly like the
elastic workers of the real tier.  The runner then plays a fixed
request workload against it over HTTP and scrapes the server's own
``/metrics`` into a private :class:`~kungfu_tpu.monitor.doctor.Doctor`
after every wave, accumulating findings the same way the elastic tier's
``_DoctorSampler`` does.

The twin contract mirrors straggler-doctor:

* ``slo-doctor`` delays every ``serving.admit`` — TTFT blows through
  the (deliberately tight) SLO, the budget-burn gauge sustains above
  threshold, and ``detect_slo`` must raise an ``slo-violation``
  finding naming the serving instance (rank 0).
* ``slo-doctor-clean`` runs the identical workload unfaulted — any
  ``slo-violation`` is a false positive.  The two warm-up requests
  absorb the jit compiles; ``KFT_SLO_WINDOW`` is sized so they roll
  out of the compliance window before the measured waves.

Single process, single host, CPU backend: this tier needs neither the
native comm library nor the multiprocess data plane, so (like the sim
tier) it runs unconditionally everywhere CI runs.
"""
from __future__ import annotations

import glob
import http.client
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from typing import List, Optional

from .runner import (Scenario, ScenarioResult, _collect_fired,
                     _free_port, doctor_violations, floor_violations)

__all__ = ["run_serving_scenario"]

# tiny model: big enough to exercise the real engine (2 layers, paged
# KV, bucketed prefill), small enough that a CPU prefill+decode round
# sits far under the clean-twin TTFT target
_SERVER_ARGS = ["--vocab", "256", "--d-model", "32", "--n-heads", "2",
                "--n-layers", "2", "--d-ff", "64", "--max-seq", "128",
                "--slots", "4", "--block", "16", "--blocks", "64",
                "--chunk", "4", "--buckets", "16", "--dtype", "float32"]
_PROMPT_LEN = 8      # <= the single 16-token prefill bucket
_MAX_NEW = 8
_WARMUP = 2          # serial: pays the prefill + decode compiles
_WAVES = 4           # one doctor scrape per wave (+ one final)
_WAVE_N = 8          # requests per wave, posted concurrently
# SLO dials exported to the server: TTFT-only (the admit delay moves
# exactly the arrival->admission leg), p90 over a window of one wave —
# warm-up compiles roll out after the first measured wave
_SLO_ENV = {"KFT_SLO_TTFT_MS": "400", "KFT_SLO_TPOT_MS": "0",
            "KFT_SLO_E2E_MS": "0", "KFT_SLO_PERCENTILE": "0.9",
            "KFT_SLO_WINDOW": str(_WAVE_N)}
_READY_S = 180.0     # interpreter + jax import + tiny-model init


def _post_generate(url: str, uid_hint: int, timeout: float) -> bool:
    body = json.dumps({
        "prompt": [(uid_hint * 7 + i) % 250 + 1
                   for i in range(_PROMPT_LEN)],
        "max_new": _MAX_NEW, "temperature": 0.0}).encode()
    req = urllib.request.Request(
        url + "/generate", data=body,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status == 200 and bool(json.load(r).get("tokens"))
    except (OSError, urllib.error.URLError, ValueError):
        return False


def _wait_ready(url: str, proc: subprocess.Popen,
                deadline: float) -> bool:
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            return False
        try:
            with urllib.request.urlopen(url + "/stats",
                                        timeout=2.0) as r:
                if r.status == 200:
                    return True
        except (OSError, urllib.error.URLError):
            pass
        time.sleep(0.25)
    return False


def run_serving_scenario(sc: Scenario,
                         out_root: Optional[str] = None,
                         verbose: bool = True) -> ScenarioResult:
    """Execute one serving-tier scenario (see module doc)."""
    from ..monitor import Monitor
    from ..monitor import cluster as _mcluster
    from ..monitor.doctor import Doctor
    from ..monitor.history import MetricsHistory

    out_dir = tempfile.mkdtemp(prefix=f"kfchaos-{sc.name}-",
                               dir=out_root)
    plan_path = os.path.join(out_dir, "plan.json")
    sc.plan.save(plan_path)
    log_prefix = os.path.join(out_dir, "chaos-log")
    port = _free_port()
    url = f"http://127.0.0.1:{port}"
    instance = f"127.0.0.1:{port}"

    env = dict(os.environ,
               KFT_CHAOS_PLAN=plan_path,
               KFT_CHAOS_LOG=log_prefix,
               KFT_TRACE_DIR=out_dir,
               JAX_PLATFORMS="cpu",
               **_SLO_ENV)
    if verbose:
        print(f"kfchaos: scenario {sc.name}: serving tier, "
              f"{_WAVES}x{_WAVE_N} requests @ {url}, "
              f"{len(sc.plan.faults)} fault(s), out {out_dir}",
              flush=True)
    server_log = open(os.path.join(out_dir, "server.log"), "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "kungfu_tpu.serving",
         "--port", str(port)] + _SERVER_ARGS,
        env=env, stdout=server_log, stderr=subprocess.STDOUT)

    # the same private-monitor discipline as _DoctorSampler: finding
    # gauges must not leak into the runner's global /metrics between
    # back-to-back scenarios
    doctor = Doctor(history=MetricsHistory(window=256),
                    monitor=Monitor())
    ranks = {instance: 0}
    seen = {}
    violations: List[str] = []

    def scrape() -> None:
        # the serving server exposes /metrics on its OWN port (no
        # MONITOR_PORT_OFFSET — that is the elastic-worker convention
        # aggregate() applies), so scrape directly into the history
        try:
            text = _mcluster.scrape("127.0.0.1", port, timeout=2.0)
        except (OSError, http.client.HTTPException):
            return   # missed sample; the next wave scrapes again
        doctor.history.observe_text(instance, text)
        for f in doctor.diagnose(ranks=ranks):
            seen.setdefault(f.key(), f.to_dict())

    rc = 1
    try:
        if not _wait_ready(url, proc, time.monotonic() + _READY_S):
            violations.append("serving server never became ready "
                              "(see server.log)")
        else:
            deadline = time.monotonic() + sc.timeout_s
            ok_n = 0
            for i in range(_WARMUP):
                ok_n += _post_generate(url, i, _READY_S)
            for wave in range(_WAVES):
                budget = max(5.0, deadline - time.monotonic())
                results = [False] * _WAVE_N
                threads = [
                    threading.Thread(
                        target=lambda j=j: results.__setitem__(
                            j, _post_generate(
                                url, _WARMUP + wave * _WAVE_N + j,
                                budget)),
                        daemon=True)
                    for j in range(_WAVE_N)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=budget)
                ok_n += sum(results)
                scrape()
            scrape()   # one last look after the final wave settled
            want = _WARMUP + _WAVES * _WAVE_N
            if ok_n < want:
                violations.append(
                    f"only {ok_n}/{want} requests completed "
                    f"successfully")
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        server_log.close()
        rc = proc.returncode if proc.returncode is not None else 1

    found = sorted(seen.values(),
                   key=lambda d: (d["kind"], str(d["rank"])))
    with open(os.path.join(out_dir, "findings.json"), "w") as f:
        json.dump(found, f, indent=2)
    if sc.doctor_expect is not None:
        violations += doctor_violations(sc.doctor_expect, found)
    fired = _collect_fired(log_prefix)
    violations += floor_violations(sc, fired, [])
    if rc != 0:
        violations.append(f"serving server exited rc={rc}")
    trace_files = sorted(
        glob.glob(os.path.join(out_dir, "kftrace.*.jsonl"))
        + glob.glob(os.path.join(out_dir, "kfrequests.*.jsonl*")))
    res = ScenarioResult(scenario=sc.name, rc=rc,
                         violations=violations, events=[],
                         fired=fired, out_dir=out_dir,
                         trace_files=trace_files, parent_port=port)
    if verbose:
        print(f"kfchaos: scenario {sc.name}: "
              f"{'OK' if res.ok else 'VIOLATIONS'} "
              f"(rc={rc}, {len(fired)} fault(s) fired, "
              f"{len(found)} finding(s), "
              f"{len(trace_files)} trace stream(s))", flush=True)
        for v in violations:
            print(f"kfchaos:   violation: {v}", flush=True)
    return res
