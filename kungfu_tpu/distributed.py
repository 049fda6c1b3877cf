"""Lifecycle of the multi-process jax.distributed DATA plane.

The reference re-forms its data plane across OS processes on every
resize: each peer rebuilds its session at the new cluster version and
collectives span the new membership (srcs/go/kungfu/peer/peer.go:227-263;
the runner diffs and spawns workers at srcs/go/kungfu/runner/watch.go:64-104).
The XLA analogue is harder because the global device set is baked into
the backend when ``jax.distributed.initialize`` runs (SURVEY §7 "hard
parts": elastic resize vs XLA's static world).  This module makes the
teardown/re-init explicit and *versioned*:

- every cluster version ``v`` gets its OWN coordinator endpoint — peer
  0's worker port + 1000 + v — derived identically by every member from
  the shared peer list.  A fresh rendezvous address per version is the
  data plane's fencing token (the analogue of the host plane's
  connection-version token, reference connection.go:77-87): a stale
  process cannot meet the new membership at the old address.
- :func:`reinit` tears the old runtime down (``jax.distributed.shutdown``
  + XLA backend clear) and initializes at the new version.  Backend
  teardown invalidates every live device array — snapshot state to host
  FIRST; :class:`kungfu_tpu.elastic.DistributedElasticTrainer` does.
- on a real TPU pod the same protocol runs one process per host; on the
  CPU test rig each process contributes
  ``--xla_force_host_platform_device_count`` virtual devices.

State re-sync across the rebuilt plane rides the native HOST plane
(:func:`broadcast_host_tree`), not XLA: a newly-joined process needs the
model before it can participate in any compiled collective.
"""
from __future__ import annotations

import os
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .utils import knobs

_COORD_PORT_OFFSET = 1000

# (version, coordinator, num_processes, process_id) of the live runtime,
# None before the first initialize
_live: Optional[Tuple[int, str, int, int]] = None
_atexit_armed = False


def _norm_peers(peers: Sequence) -> List[Tuple[str, int]]:
    out = []
    for p in peers:
        if isinstance(p, str):
            host, port = p.split(":")[:2]
            out.append((host, int(port)))
        else:  # PeerID-like
            out.append((p.host, int(p.port)))
    return out


_VERSION_WRAP = 20000


def coordinator_address(peers: Sequence, version: int) -> str:
    """The version-v rendezvous endpoint, derived identically by every
    member: peer 0's host at its worker port + 1000 + v, folded into the
    unprivileged port range.  Distinct versions map to distinct ports for
    20k consecutive versions (the fencing window — beyond it the address
    space wraps).  ``KFT_COORDINATOR`` overrides version 0 only (a static
    address cannot follow elastic membership)."""
    env = knobs.raw("KFT_COORDINATOR")
    if env and version == 0:
        return env
    host, port = _norm_peers(peers)[0]
    raw = port + _COORD_PORT_OFFSET + (version % _VERSION_WRAP)
    return f"{host}:{1024 + (raw - 1024) % (65536 - 1024)}"


def version() -> Optional[int]:
    """Cluster version of the live data plane, or None when down."""
    return _live[0] if _live is not None else None


def is_initialized() -> bool:
    return _live is not None


def require_own_chips(peers: Sequence, rank: int) -> None:
    """Fail with a message when this worker shares its host with another
    worker of the job and the backend is a TPU.

    libtpu gives a host's chips to ONE process: ``KFT_VISIBLE_CHIPS``
    reaches jax only as ``local_device_ids``, which libtpu does not use
    to divide chips, so every colocated worker opens every chip and all
    but the first die inside backend start-up with a lockfile error
    that names no cause (measured on a four-chip v5e host, PR 21).
    One worker per host (``-np 1``, or one per pod host) drives all of
    the host's chips.  Call before the first backend use; on other
    backends (the CPU rig's colocated workers) this returns."""
    hosts = [h for h, _ in _norm_peers(peers)]
    colocated = hosts.count(hosts[rank])
    if colocated < 2:
        return
    import jax
    msg = (f"{colocated} workers of this job run on host "
           f"{hosts[rank]!r} and the backend is a TPU: a host's TPU chips "
           f"belong to one process, and KFT_VISIBLE_CHIPS does not divide "
           f"them between workers. Launch one worker per host (-np 1 "
           f"drives every local chip); a multi-process data plane on one "
           f"TPU host is not supported (ROADMAP R2/S6)")
    try:
        backend = jax.default_backend()
    except RuntimeError as e:   # the loser of libtpu's lockfile
        raise RuntimeError(msg) from e
    if backend == "tpu":
        raise RuntimeError(msg)


def _clear_backends() -> None:
    import jax
    import jax.extend.backend as _eb
    _eb.clear_backends()
    jax.clear_caches()


def initialize(peers: Sequence, rank: int, cluster_version: int = 0,
               local_device_ids: Optional[Sequence[int]] = None) -> None:
    """Join the version-``cluster_version`` data plane.

    Every member must call this with the SAME peer list and version; the
    call blocks until all ``len(peers)`` processes rendezvous at the
    versioned coordinator.  After it returns, ``jax.devices()`` spans the
    whole membership.

    The runtime is brought up in RECOVERABLE mode
    (``jax_enable_recoverability``): a peer death must surface as a
    catchable error on the survivors — never the default
    terminate-the-process behavior — so the elastic shrink protocol can
    absorb it.  Heartbeat/shutdown timeouts are elastic-tuned and
    overridable via ``KFT_DATA_PLANE_HEARTBEAT_S`` /
    ``KFT_DATA_PLANE_SHUTDOWN_S``.
    """
    global _live
    import jax
    from jax._src import xla_bridge
    coord = coordinator_address(peers, cluster_version)
    n = len(_norm_peers(peers))
    if _live is not None:
        if _live[0] == cluster_version and _live[2] == n:
            return  # idempotent re-join of the live version
        raise RuntimeError(
            f"data plane live at version {_live[0]}; call reinit() (or "
            f"shutdown() first) to move to version {cluster_version}")
    if xla_bridge.backends_are_initialized():
        # a backend built before initialize() would pin the single-process
        # device set; drop it so the distributed one is built instead
        _clear_backends()
    jax.config.update("jax_enable_recoverability", True)
    # jax's preemption sync manager traps SIGTERM to defer the death to a
    # sync point — but THIS framework's preemption story is the runner's
    # (SIGTERM death -> shrink proposal -> survivors absorb it,
    # launcher/watch.py); a trapped SIGTERM would leave the worker
    # half-alive and turn the eviction into a late SIGABRT.
    jax.config.update("jax_enable_preemption_service", False)
    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=n,
        process_id=rank,
        local_device_ids=local_device_ids,
        heartbeat_timeout_seconds=knobs.get("KFT_DATA_PLANE_HEARTBEAT_S"),
        shutdown_timeout_seconds=knobs.get("KFT_DATA_PLANE_SHUTDOWN_S"))
    _live = (cluster_version, coord, n, rank)
    global _atexit_armed
    if not _atexit_armed:
        # plain init_distributed workers get the ordered teardown on
        # normal exit (see shutdown_ordered); no-op if something already
        # shut the plane down, skipped entirely on SIGTERM deaths
        # (python does not run atexit then — the preemption path)
        import atexit
        atexit.register(shutdown_ordered)
        _atexit_armed = True


def shutdown_ordered(grace_s: float = 3.0) -> None:
    """End-of-job teardown for workers WITHOUT a native host plane
    (plain :func:`kungfu_tpu.init_distributed` users): a device-plane
    barrier so every process arrives with the runtime intact, then
    non-coordinators disconnect immediately while the coordinator gives
    them ``grace_s`` to get their disconnect in before stopping the
    coordination service.  Without the ordering, the coordinator's
    process exit kills the service while peers are still disconnecting
    and they die with the client.h fatal ("Failed to disconnect from
    coordination service") — observed as a launcher job whose training
    succeeded but whose exit code didn't.  (Recoverable mode disables
    jax's own shutdown barrier for exactly the elastic reasons
    :func:`initialize` documents, so the ordering is on us.)

    Registered via atexit by :func:`initialize`; elastic trainers that
    have a native host plane sequence exactly instead
    (``elastic.multiproc._teardown_plane_ordered``) and leave this a
    no-op by shutting down first.  The barrier runs under a WATCHDOG
    (``KFT_DATA_PLANE_SHUTDOWN_S`` + heartbeat, default ~15 s): atexit
    also fires when THIS rank is dying of an unhandled exception while
    the others are blocked inside a training collective — they can
    never reach the barrier, so an unbounded wait would convert a
    one-rank crash into a cluster-wide hang.  On timeout we return
    WITHOUT disconnecting (a native disconnect under the still-blocked
    barrier thread can abort instead of erroring); the process exit
    then drops the connection, which surfaces on survivors as the same
    catchable recoverable-mode error the elastic shrink path absorbs.
    The timed-out rank's own exit may be unclean — it is the crashing
    rank."""
    global _live
    if _live is None:
        return
    import threading
    import time
    snap = _live

    def _barrier():
        try:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices(f"kft-shutdown-{snap[0]}")
        except Exception:
            pass

    timeout = (knobs.get("KFT_DATA_PLANE_SHUTDOWN_S")
               + knobs.get("KFT_DATA_PLANE_HEARTBEAT_S"))
    t = threading.Thread(target=_barrier, daemon=True)
    t.start()
    t.join(timeout=timeout)
    if t.is_alive():
        # watchdog fired: the daemon thread is still blocked inside
        # sync_global_devices, and its `except` cannot catch a
        # native-level fault — calling jax.distributed.shutdown()
        # under it can abort at exit instead of surfacing the
        # catchable recoverable-mode error.  Return WITHOUT
        # disconnecting: process exit drops the connection, which
        # surfaces on survivors as the same catchable dead-client
        # signal.  ``_live`` is left intact so an explicit later
        # shutdown() (a caller that outlives the wedge) still acts.
        return
    if snap[3] == 0 and snap[2] > 1:
        time.sleep(grace_s)
    shutdown()


def shutdown() -> None:
    """Leave the data plane and drop the XLA backends.

    Safe to call when peers already died mid-collective (preemption): an
    unclean client disconnect is absorbed by force-resetting jax's
    distributed global state, since the NEXT initialize uses a fresh
    versioned coordinator anyway.  Every live device array is invalidated.
    """
    global _live
    if _live is None:
        return
    import jax
    from jax._src import distributed as _dist
    try:
        jax.distributed.shutdown()
    except Exception:
        pass
    if _dist.global_state.client is not None:
        # unclean exit path (dead coordinator/peer): discard the
        # half-dead runtime state so a later initialize() starts clean —
        # the versioned address fences any stale service
        _dist.global_state = _dist.State()
    _clear_backends()
    _live = None


def reinit(peers: Sequence, rank: int, cluster_version: int,
           local_device_ids: Optional[Sequence[int]] = None) -> bool:
    """Move the data plane to a new cluster version: coordinated teardown
    + re-init (the XLA half of the reference's session rebuild at
    peer.go:144-166).  Returns True when a rebuild happened."""
    if _live is not None and _live[0] == cluster_version:
        return False
    shutdown()
    initialize(peers, rank, cluster_version,
               local_device_ids=local_device_ids)
    return True


def broadcast_host_tree(tree, peer=None, root: int = 0,
                        name: str = "state"):
    """Broadcast a pytree of host arrays from ``root`` over the native
    HOST plane (reference: BroadcastGlobalVariables state re-sync after
    every membership change, experimental/hook/elastic.py:62-84 — here
    the payload rides the C++ TCP/shm runtime because a fresh process
    must receive state before it can join any compiled collective).

    Every process must pass a tree of identical structure/shapes (the
    receiver's values are overwritten).  Returns the synced tree as
    numpy arrays.

    With ``KFT_TREE_ENABLE`` and at least ``KFT_TREE_MIN_PULLERS``
    receivers, the payload rides the kftree relay lane instead of
    leaf-by-leaf native broadcasts: the root publishes each leaf to
    its store, every receiver pulls from its planned parent in the
    relay tree and re-serves as leaves land (comm/tree.py) — the
    resize-sync fan-out goes O(log k) in the receiver count.  Failure
    inside the lane never mixes with a collective: a receiver whose
    parent dies falls back to a direct store pull from the root, and
    the closing barrier keeps the call collective either way."""
    import jax
    if peer is None:
        from . import native as _native
        peer = _native.installed_peer()
    if peer is None or peer.size <= 1:
        return jax.tree_util.tree_map(np.asarray, tree)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    arrs = [np.ascontiguousarray(np.asarray(leaf)) for leaf in leaves]
    from .comm import tree as _tree
    if _tree.enabled(peer.size - 1):
        plan = _tree.plan_tree(
            [r for r in range(peer.size) if r != root], [root],
            host_of=peer._host_of)
        if peer.rank == root:
            for i, a in enumerate(arrs):
                peer.save(f"kfbc:{name}:{i}", a)
            out = arrs
            _tree.record_relay_shape(plan, peer.rank)
        else:
            got = _tree.relay_pull_blobs(
                peer, plan,
                [(f"kfbc:{name}:{i}", a.dtype, a.shape)
                 for i, a in enumerate(arrs)])
            out = [g.reshape(a.shape) for g, a in zip(got, arrs)]
        # receivers may still be relaying each other's pulls: nobody
        # (the root above all) may tear its store down or move on to a
        # conflicting re-publish until the wave lands everywhere
        peer.barrier(name=f"kfbc-done:{name}")
        return jax.tree_util.tree_unflatten(treedef, out)
    out = []
    for a in arrs:
        got = peer.broadcast(a, root=root,
                             name=f"{name}:{len(out)}")
        out.append(got.reshape(a.shape))
    return jax.tree_util.tree_unflatten(treedef, out)
