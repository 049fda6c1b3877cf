"""kfprof: cluster-wide device-time attribution.

The paper's monitoring plane exists so the system can *act* on live
performance signals (srcs/go/monitor/, session/monitoring.go feeding
adaptiveStrategies.go), but until this module the repo's signal plane
stopped at host-side wall clocks: BENCH_r03..r05 is flat and nobody can
say whether the step is compute-, collective-, input- or host-bound
(ROADMAP items 3 and 5).  kfprof fuses the existing pieces — the
``jax.profiler`` wrapper (utils/trace.py), the measured ceilings
(benchmarks/roofline.py -> ROOFLINE.json), kftrace and the kfdoctor
export paths — into one attribution plane, three tiers:

**(a) Always-on step breakdown** — :class:`StepPhases` splits a step's
wall time into ``compute`` (dispatch -> block_until_ready around the
jitted call), ``collective`` (version-fence + named collective waits),
``transfer`` (the kfsnap D2H dispatch cost the step pays) and ``host``
(the remainder), published as ``kungfu_tpu_step_phase_seconds{phase}``
summaries and mirrored as kftrace events so the Chrome-trace merger
shows phase rows per rank.  Wired into the elastic trainers
(elastic/multiproc.py) and the serving decode loop (serving/engine.py,
``loop="serve"``).

**(b) Compiled cost & roofline gauges** — at (re)compile time the
trainers hand their jitted step to :func:`publish_compiled_cost`, which
runs ``fn.lower(...).compile().cost_analysis()`` (via
``utils.jax_compat.compiled_cost_analysis``; absent where the backend
has no cost model) and publishes ``kungfu_tpu_step_flops`` /
``kungfu_tpu_step_hbm_bytes`` gauges.  Each step,
:func:`publish_roofline` combines those with the measured compute phase
into ``kungfu_tpu_roofline_fraction{bound=mxu|hbm|best}`` against the
ceilings in ROOFLINE.json (``KFT_ROOFLINE`` overrides the path).
Elastic resizes re-fire the compile hook, so the gauges track the
current membership's program.

**(c) Cluster capture + attribution export** — the watcher debug port
grows ``/profile?duration_s=N`` (launcher/watch.py), which fans
:func:`profile_cluster` over every worker's metrics endpoint; each
worker's :func:`handle_profile_request` runs a guarded
``jax.profiler`` capture into ``KFT_TRACE_DIR/prof/`` and answers with
its artifact paths plus a ``kfprof_meta.json`` attribution snapshot.
``tools/kfprof_report.py`` renders the breakdown table from a live
``--url``, a captured ``--dir``, or an in-process ``--smoke`` run.
kfdoctor's ``perf`` detector (monitor/doctor.py ``detect_perf``) turns
a collapsed roofline fraction into a Finding whose kind names the
dominant phase.

Env knobs: ``KFT_ROOFLINE`` (ceilings path, default ./ROOFLINE.json),
``KFT_PROF_COST=0`` (skip the AOT cost-analysis compile),
``KFT_TRACE_DIR`` (capture root).  See docs/monitoring.md
"Profiling (kfprof)".
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

from . import MONITOR_PORT_OFFSET, Monitor, get_monitor
from .. import trace as _kftrace
from ..utils import knobs

__all__ = [
    "PHASES", "PHASE_KIND", "StepPhases", "publish_compiled_cost",
    "publish_roofline", "Ceilings", "load_ceilings", "last_attribution",
    "handle_profile_request", "profile_cluster",
]

STEP_PHASE_METRIC = "kungfu_tpu_step_phase_seconds"
FLOPS_METRIC = "kungfu_tpu_step_flops"
HBM_METRIC = "kungfu_tpu_step_hbm_bytes"
ROOFLINE_METRIC = "kungfu_tpu_roofline_fraction"
FAILURES_METRIC = "kungfu_tpu_profile_failures_total"

PHASES = ("compute", "collective", "transfer", "host")

# perf-finding kind per dominant phase (kfdoctor detect_perf): the
# transfer phase is the input/D2H pipe, hence "input-bound"
PHASE_KIND = {
    "compute": "compute-bound",
    "collective": "collective-bound",
    "transfer": "input-bound",
    "host": "host-bound",
}

ENV_ROOFLINE = "KFT_ROOFLINE"
ENV_COST = "KFT_PROF_COST"

# last published attribution, per loop — the /profile meta snapshot and
# the report tool read this instead of re-deriving it from summaries
_state_lock = threading.Lock()
_last_phases: Dict[str, Dict[str, float]] = {}
_last_cost: Tuple[float, float] = (0.0, 0.0)   # (flops, hbm bytes)
_last_roofline: Dict[str, float] = {}


class StepPhases:
    """Accumulator for one step's wall-time split.

    The caller adds what it measured (``compute``, ``collective``,
    ``transfer``); :meth:`publish` derives ``host`` as the remainder of
    the step's wall time, feeds every phase into the
    ``kungfu_tpu_step_phase_seconds{phase,loop}`` summaries, and mirrors
    the split into kftrace (category ``kfprof``) so the merged
    Chrome trace grows per-rank phase rows.  Re-usable: publish resets
    the accumulator for the next step."""

    def __init__(self, loop: str = "train",
                 monitor: Optional[Monitor] = None):
        self.loop = loop
        self._mon = monitor
        self._acc: Dict[str, float] = {}

    def add(self, phase: str, seconds: float) -> None:
        if phase not in PHASES or phase == "host":
            raise ValueError(f"unknown step phase {phase!r} "
                             f"(host is derived; known: {PHASES})")
        if seconds > 0:
            self._acc[phase] = self._acc.get(phase, 0.0) + float(seconds)

    def publish(self, wall_s: float, *, rank: Optional[int] = None,
                step: Optional[int] = None,
                version: Optional[int] = None) -> Dict[str, float]:
        """Close out one step of ``wall_s`` seconds; returns the split
        (all four phases, ``host`` = un-attributed remainder >= 0)."""
        acc, self._acc = self._acc, {}
        phases = {p: acc.get(p, 0.0) for p in PHASES if p != "host"}
        phases["host"] = max(0.0, float(wall_s) - sum(phases.values()))
        mon = self._mon if self._mon is not None else get_monitor()
        for p in PHASES:
            mon.observe(STEP_PHASE_METRIC, phases[p],
                        labels={"phase": p, "loop": self.loop})
            _kftrace.event(f"kfprof.phase.{p}", category="kfprof",
                           rank=rank, step=step, version=version,
                           dur=phases[p], attrs={"loop": self.loop})
        with _state_lock:
            _last_phases[self.loop] = dict(phases)
        return phases


def publish_compiled_cost(fn, *args, monitor: Optional[Monitor] = None,
                          **kwargs) -> Optional[Dict[str, float]]:
    """AOT-lower and compile ``fn(*args, **kwargs)`` for its XLA cost
    analysis; publish ``kungfu_tpu_step_flops`` / ``_step_hbm_bytes``
    gauges.  Call at (re)compile time — the elastic trainers re-fire it
    after every resize, so the gauges follow the live program.

    Returns ``{"flops": ..., "hbm_bytes": ...}`` or None when this jax
    cannot cost the program (old jaxlib, no cost model) or
    ``KFT_PROF_COST=0`` opted out of the extra AOT compile."""
    if not knobs.get(ENV_COST):
        return None
    mon = monitor if monitor is not None else get_monitor()
    from ..utils import jax_compat
    try:
        # lower a non-donating twin when the step donates: the aliased
        # program under-counts bytes accessed, and the throwaway AOT
        # compile would warn about donated buffers it never runs
        compiled = jax_compat.lower_for_cost_analysis(
            fn, *args, **kwargs).compile()
    except Exception as e:
        # a step that RUNS but cannot be AOT-costed (donated buffers,
        # exotic shardings, ...) must not lose the training loop
        mon.inc(FAILURES_METRIC, labels={"op": "cost"})
        print(f"kft-prof: cost analysis unavailable: {e!r}",
              file=sys.stderr)
        return None
    cost = jax_compat.compiled_cost_analysis(compiled)
    if cost is None:
        return None
    flops = float(cost.get("flops", 0.0) or 0.0)
    hbm = float(cost.get("bytes accessed", 0.0) or 0.0)
    mon.set_gauge(FLOPS_METRIC, flops)
    mon.set_gauge(HBM_METRIC, hbm)
    global _last_cost
    with _state_lock:
        _last_cost = (flops, hbm)
    _kftrace.event("kfprof.cost", category="kfprof",
                   attrs={"flops": flops, "hbm_bytes": hbm})
    return {"flops": flops, "hbm_bytes": hbm}


class Ceilings:
    """The two roofline ceilings kfprof compares against: peak matmul
    FLOP/s (the MXU line) and peak HBM bytes/s, as measured by
    benchmarks/roofline.py on this platform."""

    def __init__(self, matmul_flops: float, hbm_bytes_s: float,
                 source: str = ""):
        self.matmul_flops = float(matmul_flops)
        self.hbm_bytes_s = float(hbm_bytes_s)
        self.source = source


# path -> Ceilings | None (None = tried and failed; negative-cached so a
# missing file costs one stat per process, not one per step)
_ceilings_cache: Dict[str, Optional[Ceilings]] = {}


def load_ceilings(path: Optional[str] = None) -> Optional[Ceilings]:
    """Parse ROOFLINE.json's measured ceilings (``KFT_ROOFLINE``
    overrides the path; default ``./ROOFLINE.json``).  Returns None —
    and thereafter stays quiet — when the file is absent or carries no
    matmul/hbm rows: a box that never ran the roofline bench simply has
    no roofline gauges."""
    path = path or knobs.raw(ENV_ROOFLINE) or "ROOFLINE.json"
    if path in _ceilings_cache:
        return _ceilings_cache[path]
    ceil: Optional[Ceilings] = None
    try:
        with open(path) as f:
            doc = json.load(f)
        matmul = max((float(r.get("tflops", 0.0)) * 1e12
                      for r in doc.get("results", ())
                      if str(r.get("op", "")).startswith("matmul")),
                     default=0.0)
        hbm = max((float(r.get("gib_per_s", 0.0)) * 2 ** 30
                   for r in doc.get("results", ())
                   if "hbm" in str(r.get("op", ""))), default=0.0)
        if matmul > 0 or hbm > 0:
            ceil = Ceilings(matmul, hbm, source=path)
    except (OSError, ValueError, KeyError, TypeError):
        get_monitor().inc(FAILURES_METRIC, labels={"op": "roofline"})
    _ceilings_cache[path] = ceil
    return ceil


def publish_roofline(compute_s: float, *,
                     monitor: Optional[Monitor] = None,
                     ceilings: Optional[Ceilings] = None
                     ) -> Optional[Dict[str, float]]:
    """Combine the compiled cost gauges with this step's measured
    ``compute`` phase into ``kungfu_tpu_roofline_fraction`` gauges:
    achieved FLOP/s over the MXU ceiling (``bound="mxu"``), achieved
    HBM bytes/s over the copy ceiling (``bound="hbm"``), and their max
    (``bound="best"`` — the fraction of whichever roof the step is
    actually pushing against).  No cost analysis or no ceilings ->
    None, no gauges."""
    ceil = ceilings if ceilings is not None else load_ceilings()
    with _state_lock:
        flops, hbm = _last_cost
    if ceil is None or compute_s <= 0 or (flops <= 0 and hbm <= 0):
        return None
    out: Dict[str, float] = {}
    if flops > 0 and ceil.matmul_flops > 0:
        out["mxu"] = (flops / compute_s) / ceil.matmul_flops
    if hbm > 0 and ceil.hbm_bytes_s > 0:
        out["hbm"] = (hbm / compute_s) / ceil.hbm_bytes_s
    if not out:
        return None
    out["best"] = max(out.values())
    mon = monitor if monitor is not None else get_monitor()
    for bound, frac in out.items():
        mon.set_gauge(ROOFLINE_METRIC, frac, labels={"bound": bound})
    with _state_lock:
        _last_roofline.clear()
        _last_roofline.update(out)
    return out


def last_attribution() -> Dict[str, object]:
    """The most recent published attribution (per loop), cost gauges and
    roofline fractions — the ``kfprof_meta.json`` snapshot a capture
    ships next to its artifacts."""
    with _state_lock:
        return {
            "phases": {loop: dict(ph) for loop, ph in _last_phases.items()},
            "cost": {"flops": _last_cost[0], "hbm_bytes": _last_cost[1]},
            "roofline": dict(_last_roofline),
        }


# ------------------------------------------------------------ capture
def _parse_duration(path: str, default: float = 2.0) -> float:
    from urllib.parse import parse_qs, urlparse
    q = parse_qs(urlparse(path).query)
    try:
        dur = float(q.get("duration_s", [str(default)])[0])
    except ValueError:
        dur = default
    return max(0.05, min(dur, 120.0))


_capture_seq_lock = threading.Lock()
_capture_seq = 0


def handle_profile_request(path: str,
                           monitor: Optional[Monitor] = None
                           ) -> Dict[str, object]:
    """Worker side of ``/profile?duration_s=N`` (served by
    :class:`~kungfu_tpu.monitor.MetricsServer`): run one guarded
    ``jax.profiler`` capture of N seconds into ``KFT_TRACE_DIR/prof/``
    and answer with the artifact paths plus the current attribution
    snapshot.  Never raises — a busy or failed profiler answers
    ``{"ok": false, ...}`` (the failure is already counted on the
    monitor by utils/trace.py)."""
    global _capture_seq
    import tempfile

    from ..utils import trace as _utrace
    duration_s = _parse_duration(path)
    root = knobs.raw(_kftrace.ENV_DIR) or tempfile.gettempdir()
    with _capture_seq_lock:
        _capture_seq += 1
        seq = _capture_seq
    logdir = os.path.join(root, "prof",
                          f"capture-{os.getpid()}-{seq}")
    started = _utrace.start_capture(logdir)
    if started is None:
        return {"ok": False, "duration_s": duration_s,
                "error": "capture unavailable (another capture active "
                         "or jax.profiler failed; see "
                         "kungfu_tpu_profile_failures_total)"}
    time.sleep(duration_s)
    stopped = _utrace.stop_capture()
    if stopped is None:
        return {"ok": False, "duration_s": duration_s, "logdir": logdir,
                "error": "stop_trace failed (see "
                         "kungfu_tpu_profile_failures_total)"}
    meta_path = os.path.join(logdir, "kfprof_meta.json")
    meta = dict(last_attribution())
    meta["pid"] = os.getpid()
    meta["duration_s"] = duration_s
    try:
        os.makedirs(logdir, exist_ok=True)
        with open(meta_path, "w") as f:
            json.dump(meta, f, indent=2)
    except OSError as e:
        print(f"kft-prof: cannot write {meta_path}: {e}", file=sys.stderr)
    artifacts: List[str] = []
    for base, _dirs, files in os.walk(logdir):
        for name in files:
            artifacts.append(os.path.join(base, name))
    return {"ok": True, "duration_s": duration_s, "logdir": logdir,
            "artifacts": sorted(artifacts),
            "attribution": last_attribution()}


def profile_cluster(targets: Sequence[Tuple[str, int]],
                    duration_s: float,
                    attempt_margin_s: float = 15.0) -> Dict[str, object]:
    """Launcher side of ``/profile``: fan one capture RPC (kfguard
    client, utils/rpc.py) to every worker's metrics endpoint
    CONCURRENTLY — the captures must overlap to show the same steps —
    and merge the per-worker replies.  Unreachable workers answer
    ``{"ok": false, "error": ...}`` instead of failing the fan-out (the
    /cluster_metrics discipline)."""
    from ..utils import rpc as _rpc
    duration_s = max(0.05, min(float(duration_s), 120.0))
    results: Dict[str, dict] = {}
    lock = threading.Lock()

    def one(host: str, port: int) -> None:
        inst = f"{host}:{port}"
        url = (f"http://{host}:{port + MONITOR_PORT_OFFSET}"
               f"/profile?duration_s={duration_s:g}")
        try:
            raw = _rpc.call(url,
                            attempt_timeout=duration_s + attempt_margin_s)
            doc = json.loads(raw.decode())
        except (OSError, ValueError) as e:
            doc = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        with lock:
            results[inst] = doc

    threads = [threading.Thread(target=one, args=(h, p), daemon=True,
                                name=f"kfprof-{h}:{p}")
               for h, p in targets]
    for t in threads:
        t.start()
    deadline = time.monotonic() + duration_s + attempt_margin_s + 5.0
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    with lock:
        workers = dict(results)
    artifacts = [a for d in workers.values()
                 for a in d.get("artifacts", ())]
    ok = bool(workers) and all(d.get("ok") for d in workers.values())
    return {"ok": ok, "duration_s": duration_s, "workers": workers,
            "artifacts": artifacts}
