"""SimClusterRunner: N fake trainers under the production watcher.

The runner process is the REAL control plane end of the scenario: an
in-process :class:`~kungfu_tpu.elastic.ConfigServer`, the real
:func:`~kungfu_tpu.launcher.watch.watch_run` loop (reaping, pending
retries, ``propose_exclusion`` shrinks, lease escalation when
``KFT_LEASE_TTL_S`` is set), the kfdoctor sampler for
``doctor_expect`` scenarios, and the same event/journal collection +
:mod:`~kungfu_tpu.chaos.invariants` sweep the real tier uses.  Only
the worker payload differs: :mod:`kungfu_tpu.sim.trainer` processes
spawned with ``KFT_SIM_LITE=1`` (no jax import), which is what makes
100-process fleets practical on one small box.

Scenario timeouts are enforced HERE (a watchdog SIGKILLs the fleet and
fails the run) because a sim fleet wedged in drain consensus would
otherwise hang the harness.
"""
from __future__ import annotations

import contextlib
import glob
import json
import os
import random
import signal
import sys
import tempfile
import threading
import time
import types
from typing import List, Optional

from . import sim_wsum
from ..chaos import invariants
from ..chaos.runner import (Scenario, ScenarioResult,
                            _collect_events, _collect_fired,
                            _CrashRestartOrchestrator, _DoctorSampler,
                            _free_port, _PolicySampler,
                            doctor_violations, floor_violations,
                            policy_violations)
from ..monitor import MONITOR_PORT_OFFSET
from ..plan.hostspec import DEFAULT_WORKER_PORT

# The spawned payload: sets lite mode BEFORE any kungfu_tpu import (a
# belt to the env var's braces), then runs the fake trainer.  The
# tempdir-unique script path doubles as the no-orphans pid marker.
SIM_WORKER = (
    "import os, sys\n"
    "os.environ.setdefault('KFT_SIM_LITE', '1')\n"
    "from kungfu_tpu.sim.trainer import main\n"
    "sys.exit(main())\n"
)

# The kffleet payload (``sim_serve`` scenarios): fake serving REPLICAS
# (sim/serving.py) under the same watcher instead of fake trainers —
# same env ABI, same lite-import contract, same pid-marker trick.
SIM_SERVE_WORKER = (
    "import os, sys\n"
    "os.environ.setdefault('KFT_SIM_LITE', '1')\n"
    "from kungfu_tpu.sim.serving import main\n"
    "sys.exit(main())\n"
)

# The sim fleets' workers sit a fixed offset under the process's worker
# window (plan/hostspec: DEFAULT_WORKER_PORT, moved by KFT_BASE_PORT), so
# that processes given distinct windows run distinct fleets.  The offset
# is chosen so that at the default base BOTH the worker range (21300..)
# and the metrics range (port + MONITOR_PORT_OFFSET, 31300..) sit below
# the kernel's default ephemeral floor (net.ipv4.ip_local_port_range
# starts at 32768): a 100-process fleet makes thousands of outgoing
# heartbeat/config connections, and any of them could otherwise squat a
# metrics port as its ephemeral source port (observed as EADDRINUSE at
# n=100).  It also puts the metrics range 200 above the window's base,
# clear of the real workers there.  A base too low to have room beneath
# it keeps its fleets above its workers' metrics range.
SIM_PORT_OFFSET = -9800
SIM_PORTS = 600


def _sim_base_port(worker_base: int) -> int:
    below = worker_base + SIM_PORT_OFFSET
    if below >= 1124:
        return below
    return worker_base + MONITOR_PORT_OFFSET + 200


SIM_BASE_PORT = _sim_base_port(DEFAULT_WORKER_PORT)

# Concurrent runs in one process (pytest running two scenarios in
# threads) each need a disjoint worker range, or their metrics servers
# fight over port+offset and their /state adoption probes cross fleets.
# A cursor hands out [base, base+nprocs) slices of the process's
# SIM_PORTS, wrapping at their end and before the metrics range would
# cross the ephemeral floor.  Concurrent PROCESSES are kept apart by
# their windows; the fake trainer still degrades to serving no /metrics
# when its bind loses a race.
_BASE_LOCK = threading.Lock()
_BASE_CURSOR = [SIM_BASE_PORT]


class _ServeLoadDriver(threading.Thread):
    """Drive a :func:`~kungfu_tpu.sim.serving.synth_diurnal_schedule`
    arrival plan AT a sim serving fleet, round-robin over the replicas
    — the runner-side half of a ``sim_serve`` scenario.  Each arrival
    fires a non-streaming ``POST /generate`` on its own daemon thread
    (the replica holds the connection until the request finishes, so a
    blocking dispatch loop would serialize the offered load down to one
    slot).  Request failures are swallowed without retry: a replica
    refusing mid-kill IS the scenario, and the journal invariants are
    asserted over what the fleet actually recorded, not over what the
    driver hoped to land."""

    def __init__(self, cluster, serve_load):
        super().__init__(daemon=True, name="kfsim-serve-load")
        from .serving import synth_diurnal_schedule
        spec = dict(serve_load)
        # replicas bind their serve ports during the watcher's spawn
        # storm; hold the first arrival until the fleet is listening
        self.warmup_s = float(spec.pop("warmup_s", 1.5))
        self.seed = int(spec.get("seed", 0))
        self.offs, self.plens, self.outs = synth_diurnal_schedule(**spec)
        self.urls = [f"http://{p.host}:{p.port}/generate"
                     for p in cluster.workers]
        self.stop_event = threading.Event()
        self._lock = threading.Lock()
        self.sent = 0
        self.ok = 0
        self._threads: List[threading.Thread] = []

    def _one(self, i: int) -> None:
        import urllib.request
        # deterministic prompt content per arrival index: same seed =>
        # same prompts => the replicas' prefix caches see one stream
        rng = random.Random((self.seed << 21) ^ i)
        prompt = [rng.randrange(1, 30000) for _ in range(self.plens[i])]
        body = json.dumps({"prompt": prompt,
                           "max_new": self.outs[i]}).encode()
        req = urllib.request.Request(
            self.urls[i % len(self.urls)], data=body,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=60.0) as r:
                r.read()
        except OSError:
            return            # killed/draining replica: expected
        with self._lock:
            self.ok += 1

    def run(self) -> None:
        t0 = time.monotonic() + self.warmup_s
        for i, off in enumerate(self.offs):
            delay = t0 + off - time.monotonic()
            if delay > 0 and self.stop_event.wait(delay):
                return
            th = threading.Thread(target=self._one, args=(i,),
                                  daemon=True, name=f"kfsim-load-{i}")
            th.start()
            with self._lock:
                self.sent += 1
                self._threads.append(th)

    def stop(self) -> None:
        self.stop_event.set()
        self.join(timeout=10)
        with self._lock:
            threads = list(self._threads)
        for th in threads:
            th.join(timeout=10)


def _alloc_base_port(nprocs: int) -> int:
    with _BASE_LOCK:
        base = _BASE_CURSOR[0]
        if (base + nprocs > SIM_BASE_PORT + SIM_PORTS
                or base + nprocs + MONITOR_PORT_OFFSET >= 32768):
            base = SIM_BASE_PORT
        _BASE_CURSOR[0] = base + nprocs
        return base


class SimClusterRunner:
    """Run one ``tier="sim"`` scenario end-to-end."""

    def __init__(self, sc: Scenario, out_root: Optional[str] = None,
                 verbose: bool = True):
        if sc.tier != "sim":
            raise ValueError(f"scenario {sc.name!r} is tier="
                             f"{sc.tier!r}, not 'sim'")
        self.sc = sc
        self.out_root = out_root
        self.verbose = verbose
        self.timed_out = False

    # ----------------------------------------------------------- watchdog
    def _kill_fleet(self, out_dir: str) -> None:
        self.timed_out = True
        for pidfile in glob.glob(os.path.join(out_dir, "pid.*")):
            with contextlib.suppress(OSError, ValueError):
                with open(pidfile) as f:
                    os.kill(int(f.read().strip()), signal.SIGKILL)

    # --------------------------------------------------------------- run
    def run(self) -> ScenarioResult:
        from ..elastic import ConfigServer, put_config
        from ..launcher.job import Job
        from ..launcher.watch import watch_run
        from ..plan import Cluster, HostList, PeerID

        sc = self.sc
        out_dir = tempfile.mkdtemp(prefix=f"kfsim-{sc.name}-",
                                   dir=self.out_root)
        script = os.path.join(out_dir, "sim_worker.py")
        with open(script, "w") as f:
            f.write(SIM_SERVE_WORKER if sc.sim_serve else SIM_WORKER)
        plan_path = os.path.join(out_dir, "plan.json")
        sc.plan.save(plan_path)
        log_prefix = os.path.join(out_dir, "chaos-log")
        target = sc.target_steps * sc.batch

        env = {
            "KFT_SIM_LITE": "1",
            "KFT_CHAOS_PLAN": plan_path,
            "KFT_CHAOS_LOG": log_prefix,
            "KFT_CHAOS_OUT": out_dir,
            "KFT_CHAOS_B": str(sc.batch),
            "KFT_CHAOS_TARGET": str(target),
            "KFT_CHAOS_PROPOSE": json.dumps(
                [list(p) for p in sc.propose]),
            "KFT_CHAOS_SNAP": str(sc.snapshot_every),
            "KFT_SIM_SEED": str(sc.sim_seed),
            "KFT_SIM_STEP_S": str(sc.sim_step_s),
            "KFT_SIM_SLOW_RANKS": ",".join(
                str(r) for r in sc.sim_slow_ranks),
            "KFT_SIM_SLOW_FACTOR": str(sc.sim_slow_factor),
            "KFT_SIM_DRAIN_S": str(sc.sim_drain_s),
            "KFT_SIM_NET_BYTES": str(sc.sim_net_bytes),
            "KFT_SIM_NET_SLOW_RANKS": ",".join(
                str(r) for r in sc.sim_net_slow_ranks),
            "KFT_SIM_NET_SLOW_FACTOR": str(sc.sim_net_slow_factor),
            "KFT_NET_RATE_PERIOD_S": str(sc.sim_net_rate_period_s),
            # workers pump leases at this cadence; the TTL side goes to
            # watch_run directly (lease_ttl_s), not through env
            "KFT_HEARTBEAT_S": str(sc.sim_heartbeat_s),
        }
        # scenario knob overrides ride the worker env exactly like the
        # real tier (chaos/runner.py): SLO targets, serve slots,
        # service-time scales for the sim_serve scenarios
        env.update(sc.env)
        if self.verbose:
            print(f"kfsim: scenario {sc.name}: {sc.nprocs} fake "
                  f"workers, target {target} samples, "
                  f"{len(sc.plan.faults)} fault(s), out {out_dir}",
                  flush=True)
        cluster = Cluster.from_hostlist(
            HostList.parse(f"127.0.0.1:{sc.nprocs}"), sc.nprocs,
            base_port=_alloc_base_port(sc.nprocs))
        parent_port = sc.parent_port if sc.parent_port else _free_port()
        srv = ConfigServer().start()
        url = srv.url
        # sample the server's (epoch, version) stream into the event
        # log — feeds check_version_monotonic_across_epochs and the
        # min_config_versions floor (no restarts scheduled: the shim
        # only carries the URL)
        observer = _CrashRestartOrchestrator(
            sc, types.SimpleNamespace(url=url), out_dir)
        sampler = None
        psampler = None
        driver = None
        watchdog = threading.Timer(sc.timeout_s,
                                   self._kill_fleet, args=(out_dir,))
        watchdog.daemon = True
        try:
            put_config(url, cluster)
            observer.start()
            if sc.doctor_expect is not None:
                sampler = _DoctorSampler(cluster, out_dir)
                sampler.start()
            if sc.policy_expect is not None or sc.policy_act:
                psampler = _PolicySampler(cluster, out_dir,
                                          config_url=url,
                                          act_mode=sc.policy_act,
                                          knob_env=sc.env)
                psampler.start()
            if sc.serve_load is not None:
                driver = _ServeLoadDriver(cluster, sc.serve_load)
                driver.start()
            watchdog.start()
            # worker settings ride the Job (NOT os.environ): two
            # concurrent runs in one process must not bleed plans,
            # out-dirs, or cadences into each other's spawns
            job = Job(prog=sys.executable, args=[script],
                      config_server=url, extra_env=env)
            rc = watch_run(job, "127.0.0.1",
                           PeerID("127.0.0.1", parent_port),
                           cluster, url, poll_interval=0.2,
                           preempt_recover=True,
                           lease_ttl_s=sc.sim_lease_ttl_s)
        finally:
            watchdog.cancel()
            if driver is not None:
                driver.stop()
            if sampler is not None:
                sampler.stop()
            if psampler is not None:
                psampler.stop()
            observer.stop()
            srv.stop()
            from ..utils import rpc as _rpc
            _rpc.reset(url)

        events = _collect_events(out_dir)
        pids = [int(open(p).read().strip())
                for p in glob.glob(os.path.join(out_dir, "pid.*"))]
        violations: List[str] = []
        if self.timed_out:
            violations.append(
                f"scenario timeout after {sc.timeout_s}s (fleet "
                f"SIGKILLed by the watchdog)")
        elif rc != 0:
            violations.append(f"job exited rc={rc} (expected 0)")
        if sc.sim_serve:
            # serving fleets hold no shared training progress: journal
            # conservation + membership agreement instead of
            # single-winner/trajectory
            violations += invariants.run_serving(
                events, pids=pids, pid_marker=script)
            if driver is not None and self.verbose:
                print(f"kfsim: load driver: {driver.sent} sent, "
                      f"{driver.ok} ok", flush=True)
        else:
            violations += invariants.run_all(
                events, pids=pids,
                oracle_wsum=lambda samples: sim_wsum(
                    sc.sim_seed, samples // sc.batch),
                pid_marker=script)
        if sc.expect_violation:
            import re as _re
            matched = [v for v in violations
                       if _re.search(sc.expect_violation, v)]
            violations = [v for v in violations if v not in matched]
            if not matched:
                violations.append(
                    f"expected a violation matching "
                    f"{sc.expect_violation!r}; none tripped")
        if sc.doctor_expect:
            found = (list(sampler.seen.values())
                     if sampler is not None else [])
            active = sampler.last_active if sampler is not None else set()
            violations += doctor_violations(sc.doctor_expect, found,
                                            active=active)
        if sc.policy_expect:
            decisions = (psampler.decisions
                         if psampler is not None else [])
            violations += policy_violations(sc.policy_expect, decisions)
        if sc.act_expect is not None:
            from ..chaos.runner import act_violations
            actions = psampler.actions if psampler is not None else []
            violations += act_violations(sc.act_expect, actions)
        if (sc.policy_expect or sc.policy_act) and psampler is not None:
            # the actuation gate: the saved tick journal must replay to
            # the exact live ledger (bit-identity, not just same rank)
            # — and it must KEEP holding with an executor attached,
            # which is why actions ride the WAL, never the tick inputs
            from ..chaos.runner import _scoped_env
            from ..policy.engine import verify_replay
            try:
                # same knob env as the live engine: the replayed rules
                # must snapshot identical hysteresis/cooldown values
                with _scoped_env(psampler.knob_env):
                    errs = verify_replay(psampler.history_path,
                                         psampler.decisions)
            except (OSError, ValueError, KeyError) as e:
                errs = [f"replay failed to run: {e}"]
            violations += [f"policy replay: {e}" for e in errs]
        fired = _collect_fired(log_prefix)
        violations += floor_violations(sc, fired, events)
        res = ScenarioResult(scenario=sc.name, rc=rc,
                             violations=violations, events=events,
                             fired=fired, out_dir=out_dir,
                             parent_port=parent_port)
        if self.verbose:
            status = "PASS" if res.ok else "FAIL"
            finals = sum(1 for e in events if e.get("kind") == "final")
            print(f"kfsim: scenario {sc.name}: {status} "
                  f"({len(fired)} fault(s) fired, {len(events)} "
                  f"events, {finals} final(s))", flush=True)
            for v in violations:
                print(f"kfsim:   violation: {v}", flush=True)
        return res


def run_sim_scenario(sc: Scenario, out_root: Optional[str] = None,
                     verbose: bool = True) -> ScenarioResult:
    """Functional entry point (what
    :func:`kungfu_tpu.chaos.runner.run_scenario` dispatches to).

    ``beats_shadow_of`` scenarios run their named shadow twin right
    after and require the acting fleet's step rate to be STRICTLY
    higher — excluding the straggler must buy real wall-clock, or the
    actuation proved nothing."""
    res = SimClusterRunner(sc, out_root=out_root, verbose=verbose).run()
    if sc.beats_shadow_of and res.ok:
        from ..chaos.runner import fleet_step_rate
        from .scenarios import sim_scenarios
        twin = sim_scenarios().get(sc.beats_shadow_of)
        if twin is None:
            res.violations.append(
                f"beats-shadow gate: no scenario named "
                f"{sc.beats_shadow_of!r} to race against")
            return res
        twin_res = SimClusterRunner(twin, out_root=out_root,
                                    verbose=verbose).run()
        act_rate = fleet_step_rate(res.events)
        shadow_rate = fleet_step_rate(twin_res.events)
        if verbose:
            print(f"kfsim: beats-shadow gate: acting "
                  f"{act_rate:.2f} steps/s vs shadow "
                  f"{shadow_rate:.2f} steps/s", flush=True)
        if not twin_res.ok:
            res.violations.append(
                f"beats-shadow gate: shadow twin "
                f"{twin.name!r} itself failed: "
                f"{twin_res.violations[:3]}")
        elif act_rate <= shadow_rate:
            res.violations.append(
                f"beats-shadow gate: acting fleet {act_rate:.2f} "
                f"steps/s did not beat the shadow twin's "
                f"{shadow_rate:.2f} steps/s — the executed exclusion "
                f"bought no wall-clock")
    return res
