"""Elastic watcher: reconcile local worker processes with cluster updates.

Reference: srcs/go/kungfu/runner/watch.go:42-135 — the runner keeps a map
of current local workers; on every Stage{version, cluster} update it diffs
the local membership, kills removed workers, spawns added ones, and exits
when the cluster drains.  Stage updates arrive two ways, exactly like the
reference: PUSHED to this runner's control port (launcher/control.py, the
ConnControl analogue — one TCP round trip) with config-server polling as
the fallback for pushes that never arrive.  TPU-VM preemption notices
inject updates through the same two paths (see preemption handling in
watch_run).
"""
from __future__ import annotations

import os
import threading
import time
from typing import Callable, Dict, List, Optional

from ..chaos import point as _chaos_point
from ..plan.cluster import Cluster
from ..plan.peer import PeerID, PeerList
from ..elastic.config_server import fetch_config, fetch_health, put_config
from ..utils import knobs
from ..utils import rpc as _rpc
from .job import ChipPool, Job
from .proc import Proc


# Popen returncodes that mean "killed by an eviction-class signal":
# negative values are direct signal deaths, 128+N their shell encodings.
# SIGTERM is what TPU-VM preemption (and the watcher's own reconcile
# kills) delivers; SIGKILL follows when the VM is torn down hard.
_PREEMPT_CODES = {-15, -9, 143, 137}

# how long a worker that a stage removes has to finish the resize and
# leave before the watcher ends it (Watcher.update, reap)
LEAVE_S = 3.0


class Watcher:
    """Per-host process reconciler."""

    HISTORY_LIMIT = 64

    def __init__(self, job: Job, host: str, parent: PeerID,
                 pool: Optional[ChipPool] = None,
                 preempt_recover: bool = False):
        self.job = job
        self.host = host
        self.parent = parent
        self.pool = pool
        self.preempt_recover = preempt_recover
        self.current: Dict[PeerID, Proc] = {}
        self._chip_of: Dict[PeerID, int] = {}
        self.version = -1
        self.failed: Optional[int] = None
        # workers that died by a preemption-class signal, awaiting a
        # shrink proposal (drained by watch_run outside reap's lock)
        self.preempted: List[PeerID] = []
        self._last_cluster: Optional[Cluster] = None
        self._done: set = set()  # peers that exited cleanly this version
        # peers reaped as preempted whose exclusion CAS has not landed
        # yet: retry_pending must NOT respawn them — a respawn races the
        # watcher's own shrink proposal, and the late exclusion then
        # removes a healthy worker after survivors began finalizing
        # (observed as split final membership in 100-worker sim sweeps)
        self._condemned: set = set()
        # workers a stage removed that are still on their way out:
        # (proc, its chip, when the watcher ends it)
        self._leaving: list = []
        # applied Stage history for the debug endpoint (reference: the
        # runner's -debug-port dump, handler.go:117-122)
        self.history: List[Dict] = []
        self._lock = threading.Lock()

    def local_workers(self, cluster: Cluster) -> List[PeerID]:
        return [w for w in cluster.workers if w.host == self.host]

    def update(self, version: int, cluster: Cluster) -> None:
        """Diff-and-reconcile (reference: watch.go:64-83)."""
        with self._lock:
            if version <= self.version:
                return
            _chaos_point("launcher.watch.update", version=version)
            want = set(self.local_workers(cluster))
            have = set(self.current)
            # A worker that the new stage removes is inside the resize:
            # it commits, hands its shards over and takes the old plane
            # down together with the members that stay, and then leaves
            # by itself.  It gets LEAVE_S to do so; SIGTERM is for the
            # one that does not (reap() looks, so the loop never waits).
            leave_by = time.monotonic() + LEAVE_S
            for peer in have - want:
                _chaos_point("launcher.watch.kill", version=version)
                self._leaving.append((self.current.pop(peer),
                                      self._chip_of.pop(peer, None),
                                      leave_by))
            if want - have:
                # what the stage spawns may need their ports and chips
                self._end_leavers(float("inf"))
            self._done.clear()  # new membership version: everyone works again
            # exclusions that landed leave want; keep condemning only
            # peers still awaiting theirs (a later grow that re-adds an
            # excluded host:port is a NEW worker and must spawn)
            self._condemned &= want
            for peer in sorted(want - have):
                self._spawn(peer, cluster, version)
            self.version = version
            self._last_cluster = cluster
            self.history.append({
                "version": version,
                "time": time.time(),
                "cluster_size": cluster.size(),
                "local": [str(w) for w in sorted(want)],
            })
            del self.history[:-self.HISTORY_LIMIT]

    def _spawn(self, peer: PeerID, cluster: Cluster, version: int) -> bool:
        """Spawn one worker; False when the chip pool is exhausted (the
        spawn stays pending and retry_pending() re-attempts it)."""
        chip = self.pool.get() if self.pool else None
        if self.pool is not None and chip is None:
            # refuse an unpinned spawn: it would contend with the workers
            # already holding per-chip pins
            import sys
            print(f"[watcher] chip pool exhausted; deferring {peer}",
                  file=sys.stderr)
            return False
        _chaos_point("launcher.watch.spawn", version=version)
        proc = self.job.new_proc(peer, cluster, version, self.parent, chip)
        proc.start()
        self.current[peer] = proc
        if chip is not None:
            self._chip_of[peer] = chip
        return True

    def retry_pending(self) -> None:
        """Re-attempt spawns that were deferred on pool exhaustion."""
        with self._lock:
            if self._last_cluster is None:
                return
            want = set(self.local_workers(self._last_cluster))
            for peer in sorted(want - set(self.current) - self._done
                               - self._condemned):
                self._spawn(peer, self._last_cluster, self.version)

    def all_local_done(self) -> bool:
        """True when this host had workers and every one exited cleanly."""
        with self._lock:
            if self._last_cluster is None:
                return False
            want = set(self.local_workers(self._last_cluster))
            return bool(want) and want <= self._done

    def reap(self) -> None:
        """Collect exited workers; record failures.  With
        ``preempt_recover``, a worker killed by a preemption-class
        signal is queued for a shrink proposal instead of failing the
        job (reference contrast: watch.go:144-149 cancels the runner on
        ANY worker death; the BASELINE north star asks preemption to be
        absorbed elastically instead)."""
        with self._lock:
            self._end_leavers(time.monotonic())
            for peer, proc in list(self.current.items()):
                code = proc.poll()
                if code is None:
                    continue
                del self.current[peer]
                chip = self._chip_of.pop(peer, None)
                if chip is not None and self.pool:
                    self.pool.put(chip)
                if code == 0:
                    self._done.add(peer)
                elif self.preempt_recover and code in _PREEMPT_CODES:
                    self.preempted.append(peer)
                    self._condemned.add(peer)
                elif self.failed is None:
                    self.failed = code

    def _end_leavers(self, now: float) -> None:
        """Let go of the removed workers that have left, and end those
        whose time is up (lock held)."""
        staying = []
        for proc, chip, leave_by in self._leaving:
            if proc.poll() is None and now < leave_by:
                staying.append((proc, chip, leave_by))
                continue
            proc.kill()
            if chip is not None and self.pool:
                self.pool.put(chip)
        self._leaving = staying

    def drain(self) -> None:
        with self._lock:
            self._end_leavers(float("inf"))
            for proc in self.current.values():
                proc.kill()
            self.current.clear()

    def alive(self) -> int:
        with self._lock:
            return len(self.current)

    def settled(self) -> bool:
        """No worker left, member or on its way out."""
        with self._lock:
            return not self.current and not self._leaving


def propose_exclusion(config_url: str, dead: set, retries: int = 8
                      ) -> Optional[int]:
    """Convert dead/evacuating workers into a shrink: CAS-remove them
    from the config server's cluster and push the new Stage to every
    runner (reference shape: a membership change proposed to the config
    server, peer.go:227-263, then pushed over ConnControl,
    peer.go:190-209 — here the RUNNER originates it because the dying
    worker cannot).

    Returns the new version, the current version when another runner
    already absorbed the deaths (lost the CAS race benignly), or None
    when removing them would empty the cluster (caller should fail).

    CAS losses back off with jitter (kfguard ``rpc.Backoff``) instead
    of re-fetching in a tight loop: a 409 storm from concurrent shrink
    proposals must not hammer the server that is coordinating the very
    recovery it is part of."""
    import sys as _sys
    import urllib.error
    from .control import push_stage
    backoff = _rpc.Backoff()
    for _ in range(retries):
        version, cluster = fetch_config(config_url)
        workers = [w for w in cluster.workers if w not in dead]
        if len(workers) == len(cluster.workers):
            return version  # already absorbed by a concurrent proposal
        if not workers:
            return None
        shrunk = Cluster(cluster.runners, PeerList(workers))
        try:
            new_version = put_config(config_url, shrunk,
                                     if_version=version)
        except urllib.error.HTTPError as e:
            if e.code == 409:  # lost a CAS race: back off, re-fetch
                backoff.sleep()
                continue
            raise
        acked = push_stage(list(cluster.runners), new_version, shrunk)
        print(f"kft-run: preemption shrink v{new_version}: removed "
              f"{sorted(str(d) for d in dead)}, {len(workers)} workers "
              f"remain ({acked} runners acked the push)",
              file=_sys.stderr, flush=True)
        return new_version
    return None


def _doctor_targets(w: "Watcher"):
    """Scrape targets + instance->rank map for the doctor: the full
    cluster membership when known (remote workers' /metrics are
    reachable over the network), else the local live set."""
    with w._lock:
        cluster = w._last_cluster
        peers = (list(cluster.workers) if cluster is not None
                 else sorted(w.current))
    targets = [(p.host, p.port) for p in peers]
    ranks = {f"{p.host}:{p.port}": i for i, p in enumerate(peers)}
    return targets, ranks


def _doctor_tick(w: "Watcher", doctor, policy=None, executor=None):
    """One diagnosis pass: scrape every worker into the history ring,
    fold in the runner's own metrics (lease ages, rpc outage gauges —
    the control-plane signals), and run the detectors.  When a shadow
    policy engine rides along it sees the same scrape (the engine
    duck-types as the history sink) and evaluates right after the
    diagnosis — one tick, one consistent snapshot for both planes."""
    from ..monitor import get_monitor
    from ..monitor import cluster as _cluster
    from ..monitor.doctor import RUNNER_INSTANCE
    targets, ranks = _doctor_targets(w)
    doctor.prune_membership(ranks)
    _cluster.aggregate(
        targets, history=policy if policy is not None else doctor.history)
    doctor.observe(RUNNER_INSTANCE, get_monitor().render_metrics())
    findings = doctor.diagnose(ranks=ranks, version=w.version)
    if policy is not None:
        decisions = policy.tick(findings, ranks=ranks, version=w.version)
        if executor is not None:
            # actuation (docs/policy.md "Actuation"): the membership
            # version THIS tick evaluated under is the fence every
            # resulting action carries — the executor never refetches
            # a newer world to act in
            executor.submit(decisions, version=w.version)
    return findings


def _start_debug_server(w: "Watcher", port: int, doctor=None,
                        policy=None, executor=None):
    """HTTP endpoint dumping the runner's applied Stage history + live
    worker state (reference: runner -debug-port, handler.go:117-122),
    plus ``/cluster_metrics`` — every live worker's /metrics endpoint
    scraped and merged with per-worker instance labels — and
    ``/findings`` — the kfdoctor diagnosis (each hit scrapes one more
    snapshot into the history window and re-runs the detectors) — and
    ``/decisions`` — the shadow policy engine's ledger tail + standing
    proposals (each hit is one more doctor+policy tick) — and
    ``/profile?duration_s=N`` — a kfprof device-trace capture fanned to
    every live worker (kungfu_tpu.monitor.{cluster,doctor,profiler};
    docs/monitoring.md, docs/policy.md).
    """
    import json as _json
    from http.server import BaseHTTPRequestHandler

    from ..monitor import cluster as _cluster
    from ..monitor.doctor import Doctor
    from ..utils.http import BackgroundHTTPServer

    if doctor is None:
        doctor = Doctor()
    if policy is None:
        from ..policy.engine import PolicyEngine
        policy = PolicyEngine(history=doctor.history)

    def factory(_srv):
        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802
                if self.path.startswith("/metrics"):
                    # the RUNNER's own metrics (lease-age gauges, rpc
                    # retry counters) — /cluster_metrics below is the
                    # workers' merged view
                    from ..monitor import get_monitor
                    body = get_monitor().render_metrics().encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "text/plain")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if self.path.startswith("/cluster_metrics"):
                    with w._lock:
                        targets = [(p.host, p.port) for p in w.current]
                    body = _cluster.aggregate(
                        targets, history=doctor.history).encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "text/plain")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if self.path.startswith("/profile"):
                    # kfprof cluster capture: fan one overlapping
                    # device-trace request to every live worker's
                    # metrics endpoint and merge (monitor/profiler.py;
                    # docs/monitoring.md "Profiling (kfprof)")
                    from ..monitor import profiler as _profiler
                    dur = _profiler._parse_duration(self.path)
                    with w._lock:
                        targets = [(p.host, p.port) for p in w.current]
                    doc = _profiler.profile_cluster(targets, dur)
                    doc["version"] = w.version
                    body = _json.dumps(doc, indent=2).encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if self.path.startswith("/findings"):
                    findings = _doctor_tick(w, doctor, policy)
                    body = _json.dumps({
                        "version": w.version,
                        "findings": [f.to_dict() for f in findings],
                    }, indent=2).encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if self.path.startswith("/decisions"):
                    # policy plane (docs/policy.md): one more
                    # doctor+policy tick, then the ledger tail.  With
                    # no executor (shadow mode) this is what the engine
                    # WOULD be doing; with one, decisions carry their
                    # action WAL seq/outcome and "actions" holds the
                    # executed/fenced/vetoed records
                    _doctor_tick(w, doctor, policy, executor)
                    doc = {
                        "version": w.version,
                        "shadow": executor is None,
                        "mode": ("shadow" if executor is None
                                 else executor.mode),
                        "ticks": policy.tick_count,
                        "active": policy.active(),
                        "decisions": [d.to_dict()
                                      for d in policy.decisions()],
                    }
                    if executor is not None:
                        doc["actions"] = executor.actions()
                    body = _json.dumps(doc, indent=2).encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                with w._lock:
                    body = _json.dumps({
                        "host": w.host,
                        "version": w.version,
                        "alive": {str(p): proc.poll() is None
                                  for p, proc in w.current.items()},
                        "failed": w.failed,
                        "history": list(w.history),
                    }, indent=2).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):  # quiet
                pass
        return Handler

    # loopback like every other embedded server (the reference's debug
    # endpoint is likewise an operator-local tool); set KFT_DEBUG_BIND to
    # widen deliberately
    bind = knobs.get("KFT_DEBUG_BIND")
    srv = BackgroundHTTPServer(factory, host=bind, port=port).start()
    srv.doctor = doctor  # reachable for tests and the watch loop
    srv.policy = policy
    return srv


def watch_run(job: Job, host: str, parent: PeerID, initial: Cluster,
              config_url: Optional[str], poll_interval: float = 0.5,
              pool: Optional[ChipPool] = None,
              stop_when_empty: bool = True,
              debug_port: int = 0,
              preempt_recover: bool = True,
              lease_ttl_s: Optional[float] = None) -> int:
    """Run the elastic watch loop until the *global* cluster drains or a
    local worker fails (reference: watch.go:106-135 WatchRun).

    A host whose local share is transiently zero keeps running — it may
    receive workers on a later grow (the reference runner likewise only
    exits when the whole cluster is gone).

    Stage updates arrive two ways: PUSHED by workers to this runner's
    control port (reference ConnControl, handler.go:91-115 — resize
    latency is one TCP round trip) with config-server polling as the
    fallback for pushes that never arrive.

    Preemption handling (``preempt_recover``, default on): a worker
    killed by a preemption-class signal becomes a shrink proposal —
    survivors keep training on the reduced cluster (see
    native.recover_from_failure for the worker side).  A SIGTERM to the
    RUNNER itself (TPU-VM eviction notice) evacuates this host: its
    workers are CAS-removed from the cluster, the Stage is pushed to the
    other runners, and the runner exits 0.
    """
    import signal as _signal
    import sys as _sys
    w = Watcher(job, host, parent, pool,
                preempt_recover=preempt_recover and bool(config_url))
    wake = threading.Event()
    exited = threading.Event()
    evacuate = threading.Event()
    pushed_size = [None]  # global size from the last pushed stage
    prev_term = None
    if (preempt_recover and config_url
            and threading.current_thread() is threading.main_thread()):
        def _on_term(signum, frame):
            evacuate.set()
            wake.set()
        prev_term = _signal.signal(_signal.SIGTERM, _on_term)

    def on_push(version: int, cluster: Cluster) -> None:
        w.update(version, cluster)
        # record the pushed global size only if this stage is the newest
        # the watcher has seen — a delayed stale push must not drive the
        # stop_when_empty decision with an old (e.g. empty) cluster
        if version >= w.version:
            pushed_size[0] = cluster.size()
        wake.set()

    def on_exit() -> None:
        exited.set()
        wake.set()

    # kfdoctor (docs/monitoring.md "Diagnosis"): KFT_DOCTOR_SCRAPE_S > 0
    # makes the watch loop itself scrape + diagnose periodically (so
    # finding gauges and traces exist without anyone curling /findings);
    # KFT_PEER_PROBE_S > 0 starts the host-plane peer-latency prober.
    from ..monitor.doctor import Doctor, PeerLatencyProber
    doctor_scrape_s = knobs.get("KFT_DOCTOR_SCRAPE_S")
    doctor = Doctor() if (doctor_scrape_s > 0 or debug_port) else None
    doctor_last = -float("inf")
    # the shadow policy engine rides the doctor's tick: same scrape,
    # same findings, decisions to the ledger/gauges/traces only —
    # never to the config server (docs/policy.md "Shadow -> act")
    policy = None
    if doctor is not None:
        from ..policy.engine import PolicyEngine
        policy = PolicyEngine(history=doctor.history)
    # kfact (docs/policy.md "Actuation"): KFT_POLICY_ACT=propose|act
    # attaches the executor to the engine's tick.  Startup first
    # resolves any pending intent a previous runner crashed on —
    # fenced out or idempotently completed, never silently dropped.
    executor = None
    if policy is not None and config_url:
        from ..policy.executor import PolicyExecutor
        mode = PolicyExecutor.mode_from_env()
        if mode != "shadow":
            executor = PolicyExecutor(config_url,
                                      ledger=policy.ledger,
                                      job=job, mode=mode)
            executor.resolve_pending()
    prober = PeerLatencyProber.from_env(lambda: _doctor_targets(w)[0])
    debug = (_start_debug_server(w, debug_port, doctor=doctor,
                                 policy=policy, executor=executor)
             if debug_port else None)
    control = None
    try:
        from .control import ControlServer
        control = ControlServer(parent.port, on_push, on_exit).start()
    except OSError as e:
        # port taken (e.g. two runners on one host misconfigured to the
        # same parent id): run pull-only rather than dying
        print(f"kft-run: control port {parent.port} unavailable ({e}); "
              f"falling back to config-server polling", flush=True)
    # align the initial stage version with the config server's counter —
    # spawned workers carry the version as their fencing token, so a skew
    # here makes them mistake the CURRENT config for a resize (the
    # reference runner likewise takes Stage{version} from the server)
    version0 = 0
    if config_url:
        try:
            # bootstrap budget rides the kfguard rpc layer: per-attempt
            # timeout + one overall deadline with jittered backoff,
            # retrying conn-refused (server booting) AND 404 (no PUT
            # yet) — the two "not ready yet" classes the old hand-rolled
            # 10x0.2s loop conflated with real failures
            version0, initial = fetch_config(config_url, deadline=2.0,
                                             retry_unseeded=True)
        except (OSError, ValueError, KeyError) as e:
            # still unseeded: spawn from the provided cluster at version
            # 0; a later PUT of the same cluster costs the workers one
            # benign in-process rebuild (resize_from_url), nothing more.
            # Logged so a persistently broken server isn't silent.
            print(f"kft-run: config server {config_url} unreadable "
                  f"({e}); starting at version 0", flush=True)
    poll_failing = False  # one log line per config-server outage
    # kfguard liveness leases: workers renew a TTL lease on the config
    # server from their STEP path; a lease older than KFT_LEASE_TTL_S
    # marks a HUNG worker — alive for reap(), dead for the collective —
    # and is escalated into the same propose_exclusion shrink a
    # preemption death takes.  0 (the default) = observe-only: gauges
    # and /health stay live, no escalation (long XLA compiles between
    # steps make an unconditional default unsafe; docs/elastic.md).
    if lease_ttl_s is not None:  # explicit beats env: a caller running
        lease_ttl = lease_ttl_s  # several watch loops in one process
    else:                        # cannot share one global knob
        lease_ttl = knobs.get("KFT_LEASE_TTL_S")
    escalated: set = set()   # peers already proposed, per version
    escalated_version = -1

    def _expired_leases(health: dict) -> set:
        """Local live peers whose lease the server last saw more than
        ``lease_ttl`` seconds ago.  Peers that never registered are
        never escalated (a worker may legitimately predate its first
        heartbeat — spawn, import, compile)."""
        leases = health.get("leases", {})
        out = set()
        with w._lock:
            local = list(w.current)
        for peer in local:
            lease = leases.get(f"{peer.host}:{peer.port}")
            if lease is None:
                continue
            age = float(lease.get("age_s", 0.0))
            from ..monitor import get_monitor
            get_monitor().set_gauge(
                "kungfu_tpu_lease_age_seconds", age,
                labels={"peer": f"{peer.host}:{peer.port}"})
            if lease_ttl > 0 and age > lease_ttl:
                out.add(peer)
        return out

    try:
        w.update(version0, initial)
        global_size = initial.size()
        while True:
            w.reap()
            if w.failed is not None:  # check before retrying: a crashed
                w.drain()             # worker must not be respawned
                return w.failed
            if exited.is_set():       # pushed "exit": leave watch mode
                w.drain()
                return 0
            if evacuate.is_set():     # runner SIGTERM = host eviction
                with w._lock:
                    mine = (set(w.local_workers(w._last_cluster))
                            if w._last_cluster else set())
                if mine and config_url:
                    try:
                        propose_exclusion(config_url, mine)
                    except (OSError, ValueError):
                        # config server unreachable while we are being
                        # evicted: nothing more this host can do — the
                        # survivors' runners will shrink the dead peers
                        # away when their collectives fail
                        pass
                w.drain()
                return 0
            if w.preempted:           # dead worker(s) -> shrink proposal
                with w._lock:
                    dead, w.preempted = set(w.preempted), []
                nv = None
                if config_url:
                    try:
                        nv = propose_exclusion(config_url, dead)
                    except (OSError, ValueError):
                        # transient config-server failure (the ordinary
                        # poll below tolerates the same): re-queue and
                        # retry next loop instead of crashing the runner
                        # and orphaning the surviving workers
                        with w._lock:
                            w.preempted.extend(dead)
                        nv = -1  # sentinel: not a terminal verdict
                if nv is None:
                    # cluster would be empty (or no config server):
                    # preemption recovery cannot apply — fail like the
                    # reference runner does on worker death
                    w.failed = 1
                    continue
                if policy is not None and nv != -1:
                    # counterfactual hindsight: a shadowed exclusion
                    # target that actually died is vindicated
                    for p in dead:
                        policy.note_outcome(f"{p.host}:{p.port}",
                                            "died")
                        if executor is not None:
                            executor.note_outcome(
                                f"{p.host}:{p.port}", "died")
            w.retry_pending()
            if pushed_size[0] is not None:
                global_size = pushed_size[0]
            if config_url:
                try:
                    version, cluster = fetch_config(config_url)
                    global_size = cluster.size()
                    w.update(version, cluster)
                    poll_failing = False
                except (OSError, ValueError, KeyError) as e:
                    # transient config-server failure: keep the current
                    # workers, but say so once per outage — a dead
                    # server must not look like a quiet one
                    if not poll_failing:
                        print(f"kft-run: config server poll failing "
                              f"({e}); keeping current workers",
                              file=_sys.stderr, flush=True)
                        poll_failing = True
                else:
                    # liveness leases — only when enabled (the default
                    # watch loop must not grow an extra HTTP request
                    # per poll), and skipped while the poll itself is
                    # failing: an unreachable server says nothing
                    # about the workers
                    if lease_ttl > 0:
                        if escalated_version != w.version:
                            escalated = set()
                            escalated_version = w.version
                        try:
                            expired = _expired_leases(
                                fetch_health(config_url)) - escalated
                        except (OSError, ValueError, KeyError):
                            expired = set()  # e.g. pre-kfguard server
                        if expired:
                            print(f"kft-run: liveness lease expired "
                                  f"(> {lease_ttl}s) for "
                                  f"{sorted(str(p) for p in expired)};"
                                  f" escalating hung worker(s) into a "
                                  f"shrink", file=_sys.stderr,
                                  flush=True)
                            escalated |= expired
                            try:
                                if propose_exclusion(config_url,
                                                     expired) is None:
                                    w.failed = 1
                                    continue
                                if policy is not None:
                                    # hindsight: the lease path beat
                                    # the shadow proposal to it
                                    for p in expired:
                                        policy.note_outcome(
                                            f"{p.host}:{p.port}",
                                            "lease-excluded")
                                        if executor is not None:
                                            executor.note_outcome(
                                                f"{p.host}:{p.port}",
                                                "lease-excluded")
                            except (OSError, ValueError):
                                # server flaked between /health and
                                # the CAS: retry at the next poll
                                escalated -= expired
            if doctor_scrape_s > 0 and doctor is not None:
                now = time.monotonic()
                if now - doctor_last >= doctor_scrape_s:
                    doctor_last = now
                    _doctor_tick(w, doctor, policy, executor)
            if stop_when_empty and w.settled() and (
                    not config_url or global_size == 0
                    or w.all_local_done()):
                return 0
            wake.clear()
            wake.wait(poll_interval)  # a push cuts the wait short
    finally:
        if prev_term is not None:
            _signal.signal(_signal.SIGTERM, prev_term)
        if prober is not None:
            prober.stop()
        if control is not None:
            control.stop()
        if debug is not None:
            debug.stop()
        if executor is not None:
            executor.close()
        if policy is not None:
            policy.close()
