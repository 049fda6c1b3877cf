"""Typed registry of every ``KFT_*`` environment knob.

One place that knows each knob's name, type, default, and meaning.
Callers read knobs through :func:`get` instead of ``os.environ`` so

- a malformed value warns and falls back to the default (the
  ``KFT_BASE_PORT`` idiom from plan/hostspec.py) instead of crashing a
  worker mid-resize with a bare ``ValueError``;
- lookups happen at *call time* against an explicit mapping (default
  ``os.environ``), so per-job env overrides (``Job.extra_env``,
  launcher/job.py) and test fixtures see their own values — nothing is
  latched at import;
- ``docs/knobs.md`` is generated from this table (``make knobs-docs``)
  and the kfcheck ``knob-registry`` pass flags any raw
  ``os.environ["KFT_*"]`` read or unregistered name, so the docs and
  the code cannot drift apart.

This module is intentionally stdlib-only with no intra-package imports:
it must be importable before jax (``kungfu_tpu/__init__`` under
``KFT_SIM_LITE``) and loadable standalone by tools/gen_knob_docs.py.

Types: ``str`` | ``int`` | ``float`` | ``bool`` | ``json`` | ``intset``.
Bool parsing: ``"" / 0 / false / off / no`` (any case) are false,
anything else set is true; a ``bool`` knob with default ``None`` is
tri-state (unset means "caller decides", e.g. the flash-attention
autotune overrides).
"""
from __future__ import annotations

import dataclasses
import json as _json
import os
import sys
from typing import Dict, List, Mapping, Optional

__all__ = ["Knob", "KNOBS", "get", "raw", "generate_docs"]

_FALSEY = ("", "0", "false", "off", "no")


@dataclasses.dataclass(frozen=True)
class Knob:
    name: str
    type: str            # str | int | float | bool | json | intset
    default: object
    doc: str
    group: str
    required: bool = False   # unset raises KeyError (no sane default)
    test_only: bool = False  # fixture for the test suite; docs skip it
    native: bool = False     # read by native/src C++ (env_* helpers)


KNOBS: Dict[str, Knob] = {}
_GROUPS: List[str] = []  # declaration order, for docs


def _def(name: str, type: str, default: object, doc: str, *,
         group: str, required: bool = False, test_only: bool = False,
         native: bool = False) -> None:
    if name in KNOBS:
        raise ValueError(f"duplicate knob {name}")
    if group not in _GROUPS:
        _GROUPS.append(group)
    KNOBS[name] = Knob(name=name, type=type, default=default, doc=doc,
                       group=group, required=required,
                       test_only=test_only, native=native)


def _parse(knob: Knob, text: str) -> object:
    if knob.type == "str":
        return text
    if knob.type == "bool":
        return text.strip().lower() not in _FALSEY
    if knob.type == "int":
        return int(text)
    if knob.type == "float":
        return float(text)
    if knob.type == "json":
        return _json.loads(text)
    if knob.type == "intset":
        return {int(x) for x in text.split(",") if x.strip()}
    raise AssertionError(f"unknown knob type {knob.type!r}")


_UNSET = object()


def raw(name: str, env: Optional[Mapping[str, str]] = None) -> Optional[str]:
    """The unparsed string value, or None when unset/empty.

    Reads ``env`` (default: ``os.environ``) at call time.
    """
    KNOBS[name]  # KeyError on unregistered names: register it first
    source = os.environ if env is None else env
    value = source.get(name)
    return value if value else None


def get(name: str, env: Optional[Mapping[str, str]] = None,
        default: object = _UNSET) -> object:
    """The knob's typed value from ``env`` (default: ``os.environ``).

    Unset/empty returns the registered default (or ``default=`` when
    given); a malformed value warns on stderr and falls back the same
    way. ``required`` knobs raise KeyError when unset — they have no
    sane default and the caller's contract is "launcher always sets it".
    """
    knob = KNOBS[name]
    text = raw(name, env)
    fallback = knob.default if default is _UNSET else default
    if text is None:
        if knob.required:
            raise KeyError(f"{name} is required but unset ({knob.doc})")
        return fallback
    try:
        return _parse(knob, text)
    except (ValueError, TypeError, _json.JSONDecodeError):
        if knob.required:
            raise ValueError(f"{name}={text!r} is malformed and the knob "
                             f"has no default ({knob.doc})")
        print(f"kft: ignoring malformed {name}={text!r}; "
              f"using {fallback!r}", file=sys.stderr)
        return fallback


# ---------------------------------------------------------------------------
# The registry.  Grouped for docs/knobs.md; defaults mirror each call
# site's historical behaviour exactly.
# ---------------------------------------------------------------------------

_ABI = "Worker env ABI (set by the launcher)"
_def("KFT_SELF_SPEC", "str", None,
     "This worker's `host:port:slot` identity. Unset means singleton "
     "(non-elastic) mode.", group=_ABI)
_def("KFT_INIT_PEERS", "str", None,
     "Comma list of worker `host:port:slot` specs at spawn time; rank = "
     "index of KFT_SELF_SPEC in this list.", group=_ABI)
_def("KFT_RUNNER_LIST", "str", None,
     "Comma list of runner (launcher) endpoints.", group=_ABI)
_def("KFT_INIT_CLUSTER_VERSION", "int", 0,
     "Membership version the worker was spawned under (fencing token "
     "for stale-worker detection).", group=_ABI)
_def("KFT_ALLREDUCE_STRATEGY", "str", None,
     "Collective topology strategy (AUTO/RING/TREE/...).", group=_ABI)
_def("KFT_CONFIG_SERVER", "str", None,
     "Config-server base URL for elastic membership.", group=_ABI)
_def("KFT_PARENT_ID", "str", None,
     "Spawning runner's peer id.", group=_ABI)
_def("KFT_NUM_LOCAL_DEVICES", "int", None,
     "Per-worker local device count override.", group=_ABI)
_def("KFT_VISIBLE_CHIPS", "str", None,
     "Comma list of local accelerator chip indices assigned by the "
     "launcher's ChipPool.", group=_ABI)
_def("KFT_COORDINATOR", "str", None,
     "jax.distributed coordinator address override (honoured for "
     "cluster version 0 only).", group=_ABI)
_def("KFT_CONTROL_TOKEN", "str", None,
     "Shared secret authenticating control-plane pushes between the "
     "launcher and workers.", group=_ABI)
_def("KFT_CONTROL_BIND", "str", None,
     "Bind address for the runner control server (default all "
     "interfaces).", group=_ABI)

_CFG = "Runtime config toggles"
_def("KFT_CONFIG_ENABLE_MONITORING", "bool", False,
     "Serve Prometheus /metrics from each worker.", group=_CFG)
_def("KFT_CONFIG_ENABLE_STALL_DETECTION", "bool", False,
     "Arm the native collective stall detector.", group=_CFG)
_def("KFT_CONFIG_ENABLE_TRACE", "bool", False,
     "Gate the lightweight `utils.trace` scopes.", group=_CFG)
_def("KFT_CONFIG_MONITORING_PERIOD_MS", "int", None,
     "Native monitoring sample period in ms (passed through to "
     "workers).", group=_CFG)
_def("KFT_CONFIG_LOG_LEVEL", "str", None,
     "Log level passed through to workers.", group=_CFG)
_def("KFT_CONFIG_STARTUP_BARRIER", "bool", True,
     "Run a host-plane barrier at peer startup; 0 opts out (the first "
     "collective then provides the sync).", group=_CFG)
_def("KFT_SIM_LITE", "bool", False,
     "Prune jax imports from the package: host-plane-only processes "
     "(kfsim fake trainers) import in milliseconds.", group=_CFG)

_LAUNCH = "Launcher & control plane"
_def("KFT_BASE_PORT", "int", 31100,
     "Base of the default worker-port window (range [1124, 55000]); "
     "each parallel launch needs a distinct base.", group=_LAUNCH)
_def("KFT_SSH", "str", "ssh",
     "ssh binary used to start remote runners (tests swap in a stub).",
     group=_LAUNCH)
_def("KFT_DEBUG_BIND", "str", "127.0.0.1",
     "Bind address for the launcher's local debug/metrics HTTP "
     "endpoint.", group=_LAUNCH)
_def("KFT_LEASE_TTL_S", "float", 0.0,
     "Watcher-side liveness lease expiry age in seconds (0 disables "
     "lease escalation).", group=_LAUNCH)
_def("KFT_DOCTOR_SCRAPE_S", "float", 0.0,
     "Launcher-side doctor scrape interval; > 0 starts the kfdoctor "
     "sampler.", group=_LAUNCH)
_def("KFT_PEER_PROBE_S", "float", 0.0,
     "Host-plane peer latency probe interval; > 0 enables the prober.",
     group=_LAUNCH)

_NATIVE = "Native transport (read by native/src C++)"
_def("KFT_RECV_TIMEOUT_S", "float", 120.0,
     "Blocking-recv timeout on the host data plane.", group=_NATIVE,
     native=True)
_def("KFT_CONN_RETRIES", "int", 150,
     "Connection attempts before a peer dial fails.", group=_NATIVE,
     native=True)
_def("KFT_CONN_RETRY_MS", "int", 200,
     "Delay between connection attempts.", group=_NATIVE, native=True)
_def("KFT_SHM_MB", "int", 32,
     "Per-connection same-host shared-memory ring size; 0 disables the "
     "shm lane.", group=_NATIVE, native=True)
_def("KFT_BIND_ALL", "bool", False,
     "Bind the native listener on all interfaces instead of the spec "
     "host.", group=_NATIVE, native=True)
_def("KFT_CONFIG_USE_UNIX", "bool", True,
     "Use unix-domain sockets for same-host peers.", group=_NATIVE,
     native=True)
_def("KFT_NATIVE_LIB", "str", None,
     "Path override for libkft_comm.so (default: the copy built next "
     "to the package).", group=_NATIVE)

_DATA = "Data plane (jax.distributed)"
_def("KFT_DATA_PLANE_HEARTBEAT_S", "int", 10,
     "jax.distributed client heartbeat interval.", group=_DATA)
_def("KFT_DATA_PLANE_SHUTDOWN_S", "int", 5,
     "jax.distributed shutdown timeout; teardown waits heartbeat + "
     "this before abandoning the coordinator.", group=_DATA)

_ELASTIC = "Elastic training, snapshots & rpc"
_def("KFT_HEARTBEAT_S", "float", 2.0,
     "Worker liveness-lease renewal interval; 0 disables the sender.",
     group=_ELASTIC)
_def("KFT_SNAPSHOT_BUDGET", "float", 0.05,
     "Async snapshot publish budget as a fraction of step time.",
     group=_ELASTIC)
_def("KFT_SNAP_CHUNK_MB", "float", 64.0,
     "Store leaves larger than this are chunked into zero-copy views.",
     group=_ELASTIC)
_def("KFT_RPC_BREAKER_FAILS", "float", 3.0,
     "Consecutive transport failures before the rpc circuit breaker "
     "opens.", group=_ELASTIC)
_def("KFT_RPC_BREAKER_COOLDOWN_S", "float", 1.0,
     "Breaker cooldown before a half-open probe is let through.",
     group=_ELASTIC)

_FAST = "Store fast lane (kffast)"
_def("KFT_SHM_LANE", "bool", True,
     "Same-host shared-memory fast lane for p2p store pulls: saves "
     "with a colocated peer also land in a named /dev/shm segment and "
     "same-host pulls attach it instead of riding the socket. 0 "
     "disables (every pull uses the wire path).", group=_FAST)
_def("KFT_SHM_MIN_KB", "float", 64.0,
     "Blobs at or below this many KiB skip the shm lane — the "
     "descriptor round trip + attach only beats the socket above it.",
     group=_FAST)
_def("KFT_STREAM_DEPTH", "int", 4,
     "In-flight request window of the chunk-streamed pull lane "
     "(requests pipeline back-to-back on one connection; deserialize "
     "overlaps the wire).", group=_FAST)
_def("KFT_STREAM_PIPELINE", "bool", True,
     "Stream multi-chunk / multi-block pulls through the async p2p "
     "lane instead of one synchronous round trip per piece. 0 falls "
     "back to sequential pulls.", group=_FAST)
_def("KFT_POOL_SLOTS", "int", 4,
     "Destination-buffer pool slots per (dtype, nbytes) class for "
     "store pulls; 0 disables reuse (every pull allocates fresh).",
     group=_FAST)

_TREE = "Distribution trees (kftree)"
_def("KFT_TREE_ENABLE", "bool", True,
     "Relay-tree lane for one-to-many model distribution: when >= "
     "KFT_TREE_MIN_PULLERS pullers want the same key-set, the planner "
     "routes them through a pipelined relay tree (holders at the "
     "roots, chunks re-published cut-through) instead of k direct "
     "pulls. 0 keeps every puller on the direct path.", group=_TREE)
_def("KFT_TREE_FANOUT", "int", 2,
     "Maximum children per relay node. Higher fans shallower but "
     "splits each node's egress more ways; 2 keeps per-edge bandwidth "
     "at half a node's egress with O(log2 k) depth.", group=_TREE)
_def("KFT_TREE_MIN_PULLERS", "int", 2,
     "Fewer concurrent pullers than this and the tree lane is skipped "
     "(a lone puller gains nothing from relaying).", group=_TREE)
_def("KFT_TREE_WAIT_S", "float", 20.0,
     "Relay patience: how long a child retries a chunk its parent "
     "does not have yet before abandoning the parent and pulling the "
     "remainder directly from a holder root.", group=_TREE)

_TRACE = "Tracing, metrics & profiling"
_def("KFT_TRACE", "bool", False,
     "Arm the kftrace flight-recorder ring at import.", group=_TRACE)
_def("KFT_TRACE_DIR", "str", None,
     "Directory for per-worker JSONL trace streams (implies the ring); "
     "also the root for profiler captures.", group=_TRACE)
_def("KFT_TRACE_RING", "int", 4096,
     "Flight-recorder ring capacity in events.", group=_TRACE)
_def("KFT_METRIC_MAX_LABELSETS", "int", 256,
     "Per-metric labelset cardinality cap; new labelsets beyond it are "
     "dropped with a warning.", group=_TRACE)
_def("KFT_ROOFLINE", "str", None,
     "Path to measured roofline ceilings (default ./ROOFLINE.json).",
     group=_TRACE)
_def("KFT_PROF_COST", "bool", True,
     "Run the AOT cost-analysis compile for compiled-cost gauges; 0 "
     "skips it.", group=_TRACE)
_def("KFT_NET_RATE_PERIOD_S", "float", 1.0,
     "kfnet: RateCounter sampling-window period for the per-target "
     "egress/ingress rate gauges (scrape cadence rolls the windows).",
     group=_TRACE)

_DOCTOR = "Doctor thresholds (kfdoctor)"
_def("KFT_DOCTOR_SKEW", "float", 1.5,
     "Straggler: rank step-p50 over cluster median.", group=_DOCTOR)
_def("KFT_DOCTOR_WINDOWS", "int", 3,
     "Consecutive evidence windows required for a finding.",
     group=_DOCTOR)
_def("KFT_DOCTOR_REGRESS", "float", 2.0,
     "Interference: recent p50 over own rolling baseline.",
     group=_DOCTOR)
_def("KFT_DOCTOR_LEASE_S", "float", 10.0,
     "Control plane: lease age alarm threshold.", group=_DOCTOR)
_def("KFT_DOCTOR_OUTAGE_S", "float", 5.0,
     "Control plane: rpc outage alarm threshold.", group=_DOCTOR)
_def("KFT_DOCTOR_MISSES", "float", 3.0,
     "Control plane: heartbeat-miss growth alarm.", group=_DOCTOR)
_def("KFT_DOCTOR_STALE_S", "float", 60.0,
     "Ignore instances not scraped within this window.", group=_DOCTOR)
_def("KFT_DOCTOR_ROOFLINE", "float", 0.05,
     "Perf: roofline-fraction floor.", group=_DOCTOR)
_def("KFT_DOCTOR_ROOFLINE_DROP", "float", 2.0,
     "Perf: required drop vs own baseline.", group=_DOCTOR)
_def("KFT_DOCTOR_BURN", "float", 2.0,
     "SLO: sustained error-budget burn rate that raises an "
     "slo-violation finding.", group=_DOCTOR)
_def("KFT_DOCTOR_SLOWLINK", "float", 4.0,
     "Slowlink: cluster-median pull bandwidth over an instance's, "
     "required in every evidence window.", group=_DOCTOR)
_def("KFT_DOCTOR_SLOWLINK_MIN_BPS", "float", 1024.0,
     "Slowlink: idle-cluster floor — windows whose median pull "
     "bandwidth sits below this are inconclusive.", group=_DOCTOR)
_def("KFT_FLEET_OUTLIER_SKEW", "float", 2.0,
     "Replica outlier: one serving replica's TTFT/queue-wait p50 over "
     "the fleet lower-median, required in every evidence window.",
     group=_DOCTOR)
_def("KFT_FLEET_BURN", "float", 2.0,
     "Fleet SLO: sustained count-weighted aggregate budget-burn rate "
     "that raises a fleet-slo finding.", group=_DOCTOR)
_def("KFT_FLEET_IMBALANCE", "float", 2.0,
     "Imbalance: fleet-median admitted-load growth over a replica's, "
     "required in every evidence window (with the replica's queue "
     "wait above the fleet median — slow, not idle).", group=_DOCTOR)

_POLICY = "Policy engine (kfpolicy) and actuation (kfact)"
_def("KFT_POLICY_HYSTERESIS", "int", 2,
     "Consecutive evaluations a finding must hold before a rule "
     "would act (the build-up logs a suppressed decision).",
     group=_POLICY)
_def("KFT_POLICY_CLEAR_HYSTERESIS", "int", 6,
     "Consecutive clean evaluations before an active shadow proposal "
     "is withdrawn (and annotated spurious) — a scrape flake must "
     "not read as recovery.", group=_POLICY)
_def("KFT_POLICY_COOLDOWN_S", "float", 300.0,
     "Rate limiter: minimum gap, in snapshot time, between exclusion "
     "proposals.", group=_POLICY)
_def("KFT_POLICY_MAX_PROPOSALS", "int", 1,
     "Rate limiter: concurrent shadow exclusion proposals the "
     "straggler rule may hold.", group=_POLICY)
_def("KFT_POLICY_RING", "int", 512,
     "Bounded in-memory decision ring served by /decisions.",
     group=_POLICY)
_def("KFT_POLICY_GNS_BATCH", "int", 8,
     "GNS rule: per-worker batch size the critical-batch heuristic "
     "divides the gradient-noise scale by.", group=_POLICY)
_def("KFT_POLICY_GNS_DEADBAND", "float", 2.0,
     "GNS rule: factor the power-of-two worker-count target must "
     "differ from the fleet by before a recommendation fires.",
     group=_POLICY)
_def("KFT_POLICY_ACT", "str", "shadow",
     "Actuation mode ladder: `shadow` (engine records only, no "
     "executor), `propose` (executor emits the full fenced/journaled "
     "record but executes nothing), `act` (would-act decisions drive "
     "the real control plane).", group=_POLICY)
_def("KFT_POLICY_KILL_SWITCH", "bool", False,
     "Global actuation kill-switch, read at dispatch time — flipping "
     "it mid-tick vetoes every in-flight would-act before its CAS.",
     group=_POLICY)
_def("KFT_POLICY_ACT_BUDGET", "int", 1,
     "Per-rule executed-action budget; exhaustion journals `vetoed`, "
     "never silence. Restored from the action WAL on restart "
     "(0 disables the cap).", group=_POLICY)
_def("KFT_POLICY_ACT_COOLDOWN_S", "float", 300.0,
     "Per-rule wall-clock cooldown between executed actions; the "
     "last-executed timestamp survives restart via WAL replay.",
     group=_POLICY)
_def("KFT_POLICY_ACT_WAL", "str", None,
     "Action WAL path override; default derives from KFT_TRACE_DIR "
     "(unset and no trace dir: in-memory only).", group=_POLICY)

_CHAOS = "Chaos (kfchaos)"
_def("KFT_CHAOS_PLAN", "str", None,
     "Fault-plan JSON path, armed once at import.", group=_CHAOS)
_def("KFT_CHAOS_LOG", "str", None,
     "Journal path prefix; fires append to `<prefix>.<pid>`.",
     group=_CHAOS)
_def("KFT_CHAOS_OUT", "str", None, required=True,
     doc="Scenario output directory for the chaos/sim worker "
     "(progress journal, state dumps). The scenario runner always "
     "sets it.", group=_CHAOS)
_def("KFT_CHAOS_B", "int", 8,
     "Per-step global batch size of the chaos/sim worker.",
     group=_CHAOS)
_def("KFT_CHAOS_TARGET", "int", None, required=True,
     doc="Total sample target the chaos/sim worker trains to. The "
     "scenario runner always sets it.", group=_CHAOS)
_def("KFT_CHAOS_PROPOSE", "json", [],
     "JSON list of `[step, new_size]` resize proposals the worker "
     "submits.", group=_CHAOS)
_def("KFT_CHAOS_SNAP", "str", "1",
     "Snapshot cadence in steps, or `auto` for the budget-tuned "
     "cadence.", group=_CHAOS)
_def("KFT_CHAOS_RECOVER_S", "float", 60.0,
     "Recovery deadline the chaos worker allows a torn collective "
     "before giving up.", group=_CHAOS)

_SIM = "Simulation (kfsim)"
_def("KFT_SIM_SEED", "int", 0,
     "Deterministic per-fleet jitter seed.", group=_SIM)
_def("KFT_SIM_STEP_S", "float", 0.05,
     "Synthetic step duration.", group=_SIM)
_def("KFT_SIM_POLL_S", "float", 0.25,
     "Config-server poll interval of the fake trainer.", group=_SIM)
_def("KFT_SIM_DRAIN_S", "float", 90.0,
     "Drain deadline the fake trainer allows a pending resize.",
     group=_SIM)
_def("KFT_SIM_SLOW_RANKS", "intset", frozenset(),
     "Comma list of ranks scripted as stragglers.", group=_SIM)
_def("KFT_SIM_SLOW_FACTOR", "float", 8.0,
     "Step-time multiplier applied to the scripted stragglers.",
     group=_SIM)
_def("KFT_SIM_FLAP_PERIOD", "int", 0,
     "Scripted stragglers alternate slow/normal every N steps "
     "(0: steadily slow) — the flapping twin the actuation rate "
     "limiter must hold steady against.", group=_SIM)
_def("KFT_SIM_NET_BYTES", "int", 0,
     "kfnet sim: synthetic per-peer transfer bytes each fake-trainer "
     "step publishes into its egress/ingress counters (0 disables).",
     group=_SIM)
_def("KFT_SIM_NET_PEERS", "int", 6,
     "kfnet sim: how many neighbouring peers each fake trainer "
     "exchanges synthetic bytes with (bounds matrix cardinality).",
     group=_SIM)
_def("KFT_SIM_NET_SLOW_RANKS", "intset", frozenset(),
     "kfnet sim: comma list of ranks scripted with a throttled pull "
     "path (their ingress counters advance slower).", group=_SIM)
_def("KFT_SIM_NET_SLOW_FACTOR", "float", 8.0,
     "kfnet sim: ingress-byte divisor applied to the scripted "
     "slowlink ranks.", group=_SIM)
_def("KFT_SIM_SERVE_SLOTS", "int", 4,
     "Serving sim: concurrent decode slots of a fake replica (queue "
     "wait is the admission-semaphore wait).", group=_SIM)
_def("KFT_SIM_SERVE_PREFILL_MS", "float", 0.5,
     "Serving sim: synthetic prefill milliseconds per non-reused "
     "prompt token.", group=_SIM)
_def("KFT_SIM_SERVE_DECODE_MS", "float", 5.0,
     "Serving sim: synthetic decode milliseconds per output token.",
     group=_SIM)
_def("KFT_SIM_SERVE_SLOW_RANKS", "intset", frozenset(),
     "Serving sim: comma list of replica ranks scripted with "
     "throttled service times (the imbalance/outlier signal).",
     group=_SIM)
_def("KFT_SIM_SERVE_SLOW_FACTOR", "float", 4.0,
     "Serving sim: service-time multiplier applied to the scripted "
     "slow replicas.", group=_SIM)
_def("KFT_SIM_SERVE_PREEMPT_EVERY", "int", 0,
     "Serving sim: force one preempt/re-admit on every Nth request "
     "(0 disables) — exercises the exactly-once fleet-join contract.",
     group=_SIM)
_def("KFT_SIM_STATE_SERVE_S", "float", 0.0,
     "Grow-wave sim: synthetic service time a fake trainer spends per "
     "/state adoption it serves, serialized per donor (models a "
     "single egress NIC). Makes sequential-vs-tree wave timing "
     "measurable; 0 disables.", group=_SIM)

_BENCH = "Benchmarks"
_def("KFT_SCALING_OUT", "str", None,
     "Output directory for the scaling benchmark's per-size runs.",
     group=_BENCH)

_SLO = "Serving SLOs & request journal"
_def("KFT_SLO_TTFT_MS", "float", 2000.0,
     "SLO: time-to-first-token target in ms (0 disables the "
     "objective).", group=_SLO)
_def("KFT_SLO_TPOT_MS", "float", 200.0,
     "SLO: per-output-token decode latency target in ms (0 disables "
     "the objective).", group=_SLO)
_def("KFT_SLO_E2E_MS", "float", 10000.0,
     "SLO: end-to-end request latency target in ms, first arrival to "
     "finish (0 disables the objective).", group=_SLO)
_def("KFT_SLO_PERCENTILE", "float", 0.95,
     "Fraction of requests in the compliance window each objective "
     "must satisfy (the error budget is 1 - this).", group=_SLO)
_def("KFT_SLO_WINDOW", "int", 64,
     "Compliance window: number of most recently finished requests "
     "the SLO gauges are computed over.", group=_SLO)
_def("KFT_SLO_JOURNAL_RING", "int", 1024,
     "In-memory request-journal ring capacity (finished requests kept "
     "for /requests).", group=_SLO)
_def("KFT_SLO_JOURNAL_MB", "float", 16.0,
     "Rotate the kfrequests JSONL sink under KFT_TRACE_DIR once it "
     "exceeds this size (one .1 generation is kept).", group=_SLO)

_LOAD = "Load harness (kfload)"
_def("KFT_LOAD_TIMEOUT_S", "float", 120.0,
     "Per-request client timeout of the kfload generators.",
     group=_LOAD)
_def("KFT_LOAD_SEED", "int", 0,
     "Seed for kfload's Poisson arrivals and prompt mixes.",
     group=_LOAD)

_TESTS = "Test fixtures"
_def("KFT_TESTS_DATA_PLANE", "bool", None, test_only=True,
     doc="Force the data-plane capability probe on/off (tri-state; "
     "unset probes).", group=_TESTS)
_def("KFT_TESTS_DATA_PLANE_CACHE", "bool", True, test_only=True,
     doc="Cache the data-plane probe result on disk.", group=_TESTS)
_def("KFT_TESTS_CACHE_DIR", "str", None, test_only=True,
     doc="Directory for the probe cache (default tmpdir).",
     group=_TESTS)
_def("KFT_PERF_ENFORCE", "bool", False, test_only=True,
     doc="Make perf-sensitive tests fail (instead of skip) on timing "
     "regressions.", group=_TESTS)
_def("KFT_SLOW_TESTS", "bool", False, test_only=True,
     doc="Run the `slow` pytest tier.", group=_TESTS)


def generate_docs() -> str:
    """Render docs/knobs.md from the registry (see tools/gen_knob_docs.py).

    Deterministic: groups in declaration order, knobs sorted by name
    within each group; ``test_only`` knobs are skipped.
    """
    lines = [
        "# Environment knobs",
        "",
        "<!-- GENERATED FILE — do not edit. Regenerate with"
        " `make knobs-docs`; the table lives in"
        " kungfu_tpu/utils/knobs.py. -->",
        "",
        "Every `KFT_*` knob routes through the typed registry in",
        "[`kungfu_tpu/utils/knobs.py`](../kungfu_tpu/utils/knobs.py):"
        " malformed values",
        "warn on stderr and fall back to the default; lookups are"
        " call-time, so",
        "per-job overrides (`Job.extra_env`) behave. The kfcheck"
        " `knob-registry`",
        "pass keeps this file honest (docs/static-analysis.md).",
        "",
    ]
    for group in _GROUPS:
        rows = [k for k in sorted(KNOBS.values(), key=lambda k: k.name)
                if k.group == group and not k.test_only]
        if not rows:
            continue
        lines += [f"## {group}", "",
                  "| Knob | Type | Default | Meaning |",
                  "|---|---|---|---|"]
        for k in rows:
            if k.required:
                default = "*(required)*"
            elif k.default is None:
                default = "unset"
            elif isinstance(k.default, frozenset):
                default = "empty"
            else:
                default = f"`{k.default}`"
            doc = k.doc
            if k.native:
                doc += " *(read by the native C++ transport.)*"
            lines.append(f"| `{k.name}` | {k.type} | {default} | {doc} |")
        lines.append("")
    hidden = sorted(k.name for k in KNOBS.values() if k.test_only)
    lines += [f"*{len(hidden)} test-only fixtures "
              f"({', '.join(f'`{n}`' for n in hidden)}) are registered "
              "but not operator-facing; see the registry source.*", ""]
    return "\n".join(lines)
