"""kungfu_tpu — a TPU-native adaptive distributed ML framework.

A from-scratch rebuild of the capabilities of KungFu (Young768/KungFu) for
TPU: distributed optimizers (sync SGD, synchronous model averaging, pair
averaging, adaptive), a collective engine compiled to XLA over ICI/DCN
meshes, elastic cluster membership with a config server, online monitoring
(throughput, gradient noise scale), and a launcher.

Where the reference runs a Go socket runtime under TF/Torch ops, this
framework runs `jax.lax` collectives inside jitted, shard_mapped training
steps — the communication schedule is compiled, not interpreted.
"""
from __future__ import annotations

import time as _time

_import_began = _time.perf_counter()

import os as _os  # noqa: E402

from .utils import knobs as _knobs  # noqa: E402

# kfsim lite mode: the fake trainers of kungfu_tpu/sim/ run hundreds of
# control-plane-only processes on one box and must not pay the jax import
# (~1 s CPU each, serialised on a small machine).  With KFT_SIM_LITE=1
# only the host-plane surface (plan/, elastic config client, launcher,
# monitor, store, chaos) is importable; Session/training stay out.
_SIM_LITE = bool(_knobs.get("KFT_SIM_LITE"))

if not _SIM_LITE:
    from . import comm, plan
    from .comm import Session
    from .training import (broadcast_variables, build_train_step,
                           build_train_step_with_state, init_opt_state, lane,
                           lane_mean, replicate)
else:
    from . import plan
from .plan import Cluster, HostList, PeerID, PeerList, Strategy

__version__ = "0.1.0"

_default_session = None


def _ensure_session() -> Session:
    global _default_session
    if _default_session is None:
        _default_session = Session()
    return _default_session


def init(session: Session = None) -> Session:
    """Initialise the default session (reference: kungfu_python_init,
    srcs/cpp/src/python/init.cpp:10-41)."""
    global _default_session
    _default_session = session if session is not None else Session()
    return _default_session


def current_session() -> Session:
    return _ensure_session()


def _worker_env():
    from .launcher import env as E
    return E.from_env()


def _live_peer():
    """The already-running native peer, if any — the live cluster view
    (tracks elastic resizes and explicit native.use_peer installs), which
    the static KFT_* env cannot."""
    from . import native as _native
    return _native.installed_peer()


def init_distributed(local_device_ids=None) -> bool:
    """Initialize jax's distributed runtime from the KFT_* env ABI.

    Call at the top of a launcher-spawned worker, BEFORE any jax device
    use, to make ``jax.devices()`` span the whole cluster (multi-host TPU).
    The coordinator is the versioned rendezvous endpoint of
    :mod:`kungfu_tpu.distributed` (peer 0's worker port + 1000 + cluster
    version, identical on every worker; ``KFT_COORDINATOR`` overrides at
    version 0).  Singleton mode is a no-op (returns False): on a plain
    TPU pod VM set, use jax.distributed directly or launch via kft-run.

    Elastic jobs that must RESIZE this data plane at runtime should use
    :class:`kungfu_tpu.elastic.DistributedElasticTrainer` (or the
    :mod:`kungfu_tpu.distributed` primitives directly): a resize is a
    coordinated ``distributed.reinit`` at the new cluster version.

    Reference analogue: the worker-side half of the bootstrap that the Go
    runtime does over its TCP plane (peer.go:87-104 Start + first
    Barrier); here the rendezvous is jax's coordinator service and the
    collectives are XLA's.
    """
    we = _worker_env()
    if we.singleton or len(we.peers) <= 1:
        return False
    from . import distributed as D
    if D.is_initialized():
        return True
    if local_device_ids is None and we.chip_ids is not None:
        local_device_ids = we.chip_ids
    D.require_own_chips(list(we.peers), we.rank())
    D.initialize(list(we.peers), we.rank(), we.cluster_version,
                 local_device_ids=local_device_ids)
    return True


def current_rank() -> int:
    """Rank of this worker (reference:
    srcs/python/kungfu/python/__init__.py current_rank).

    Priority: live native peer → KFT_* env ABI (launcher-spawned worker)
    → jax process index (multi-host) / 0 (singleton)."""
    p = _live_peer()
    if p is not None:
        return p.rank
    we = _worker_env()
    if not we.singleton:
        return we.rank()
    import jax
    return jax.process_index()


def current_cluster_size() -> int:
    """Number of workers in the cluster: live native peer first, then the
    KFT_* env ABI, else the default session's lane count."""
    p = _live_peer()
    if p is not None:
        return p.size
    we = _worker_env()
    if not we.singleton:
        return we.size()
    return _ensure_session().size


def current_local_rank() -> int:
    we = _worker_env()
    if not we.singleton:
        return we.peers.local_rank(we.self_spec)
    import jax
    return 0 if jax.process_count() == 1 else jax.process_index()


def current_local_size() -> int:
    we = _worker_env()
    if not we.singleton:
        return we.peers.local_size(we.self_spec)
    import jax
    return len(jax.local_devices())


def run_barrier() -> None:
    """Cluster-wide barrier.  Launcher-spawned workers rendezvous over the
    native host runtime; singleton mode barriers the local session's lanes
    (reference: run_barrier, python/__init__.py:66-69)."""
    from . import native as _native
    p = _native.default_peer()
    if p is not None:
        p.barrier()
        return
    _ensure_session().barrier()


def detached() -> bool:
    """True when this peer was removed by a resize (see kungfu_tpu.elastic)."""
    from .elastic import state as _es
    return _es.is_detached()


def uid() -> str:
    """Globally-unique worker identity ``host:port:initVersion``
    (reference: peer.go:121-125 UID, exposed via python/__init__.py uid)."""
    we = _worker_env()
    if we.singleton:
        import os as _os

        import jax
        # pid disambiguates concurrent single-process runs on one host —
        # the reference's host:port:initVersion triple is unique because
        # port is; singleton mode has no port, so borrow the pid
        return f"localhost:{_os.getpid()}:{jax.process_index()}"
    p = we.self_spec
    return f"{p.host}:{p.port}:{we.cluster_version}"


def propose_new_size(new_size: int) -> bool:
    """Propose a new cluster size by PUTting a resized cluster to the
    config server named in the KFT_* env ABI (reference: ProposeNewSize,
    peer/legacy.go:18-38; op wrapper adapt.py).  Returns True on success;
    workers then pick the change up via elastic resize-from-URL polling."""
    we = _worker_env()
    url = we.config_server
    if not url:
        raise RuntimeError("propose_new_size: no KFT_CONFIG_SERVER set")
    import urllib.error

    from .elastic import config_server as _cs
    try:
        # routed through the kfguard rpc layer (utils/rpc.py): breaker,
        # classification, epoch check — every failure class lands in
        # the OSError family caught below
        version, cluster = _cs.fetch_config(url)
        resized = cluster.resize(int(new_size))
        # CAS on the fetched version: a concurrent proposal (409) loses
        # cleanly instead of silently overwriting the winner's layout
        new_version = _cs.put_config(url, resized, if_version=version)
    except (urllib.error.URLError, OSError, TimeoutError):
        return False
    # push the new stage straight to every runner (reference: propose
    # notifies runners over ConnControl, peer.go:190-209) — the resize
    # then lands in one TCP round trip instead of a poll interval;
    # unreachable runners still converge via their config-server poll
    if we.runners:
        from .launcher.control import push_stage
        push_stage(we.runners, new_version, resized)
    return True


def check_interference(threshold: float = 0.8, vote: bool = False) -> bool:
    """Interference check (reference: python/__init__.py
    check_interference, session/adaptiveStrategies.go:61-121).

    Default: the LOCAL threshold test — any monitored collective's
    throughput below ``threshold`` x its reference rate.  Safe to call
    from any single process (logging, dashboards).

    ``vote=True`` (multi-controller jobs): cluster-wide MAJORITY vote
    over the host plane — more than half the processes must observe
    interference, so one slow process cannot flip the whole cluster.
    This is a COLLECTIVE: every process must make the matching call."""
    s = _ensure_session()
    if vote:
        return s.check_interference_global(threshold)
    return s.check_interference(threshold)


def calc_stats():
    """Per-strategy throughput snapshot (reference: calc_stats)."""
    return _ensure_session().calc_stats()


def log_stats() -> str:
    return _ensure_session().log_stats()


def print_stats() -> None:
    """Print per-strategy throughput stats (reference: print_stats)."""
    print(log_stats())


__all__ = [
    "Session", "Cluster", "HostList", "PeerID", "PeerList", "Strategy",
    "comm", "plan", "init", "init_distributed", "current_session",
    "current_rank",
    "current_cluster_size", "current_local_rank", "current_local_size",
    "run_barrier", "detached", "uid", "propose_new_size",
    "check_interference", "calc_stats", "log_stats", "print_stats",
    "broadcast_variables", "build_train_step",
    "build_train_step_with_state", "init_opt_state", "lane", "lane_mean",
    "replicate",
]

# how long this import took, jax and the rest it pulls included when this
# is the process's first use of them: the program's own part of a set-up
# (the benchmark's `setup_import_s`)
import_seconds = _time.perf_counter() - _import_began
