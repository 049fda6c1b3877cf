"""Elastic training over a LIVE multi-process jax.distributed data plane.

This is the TPU answer to the reference's hardest capability: a resize
re-forms the data plane across OS processes — every peer rebuilds its
session at the new cluster version and collectives span the new
membership (srcs/go/kungfu/peer/peer.go:227-263, runner diff/spawn at
srcs/go/kungfu/runner/watch.go:64-104).  Here the data plane is XLA
(one jax process per host, devices spanning the cluster), so a resize is

    drain step -> snapshot state to host -> native host-plane rebuild
    (resize_from_url: digest consensus, token fencing, detach) ->
    jax.distributed shutdown + re-init at version v+1 (fresh versioned
    coordinator, kungfu_tpu.distributed) -> host-plane state broadcast
    from rank 0 -> mesh + step rebuild -> keep training.

Removed workers see ``detached`` and exit; preempted (killed) workers
surface as a failed collective on the survivors, who recover through the
same path (native.recover_from_failure) and REDO the interrupted step
from the last committed host snapshot.

Single-process-per-job elastic (one controller, lanes = devices) is
:class:`kungfu_tpu.elastic.ElasticTrainer`; this class is its
multi-process sibling for real pods.
"""
from __future__ import annotations

from typing import Callable, Optional

import numpy as np

from .. import distributed as D
from .. import native
from ..chaos import point as _chaos_point
from ..launcher import env as E
from ..trace import event as _trace_event, span as _trace_span
from . import state as _flags
from .config_server import fetch_config
from .snapshot import AsyncCommitter


def _snapshot_budget(default: float = 0.05) -> float:
    """KFT_SNAPSHOT_BUDGET as a float — a typo in an env var must
    degrade the cadence derivation (registry warn-and-fallback), not
    crash the trainer mid-step."""
    from ..utils import knobs
    return max(knobs.get("KFT_SNAPSHOT_BUDGET", default=default), 1e-6)


class DistributedElasticTrainer:
    """Synchronous data-parallel training whose process membership can
    change at runtime.

    Per step: (1) a version FENCE over the native host plane — an
    allreduce-MAX of each process's latest config-server version — so
    every member agrees whether to step or resize first (the reference
    fences every cluster change with a consensus round, peer.go:186);
    (2) the jitted DP step over the global device mesh (params replicated,
    batch sharded over devices, gradient pmean compiled by XLA); (3) at
    the commit cadence, an INITIATED host snapshot of the new state —
    kfsnap (elastic/snapshot.py) dispatches every device buffer's
    ``copy_to_host_async`` and a background committer joins and
    publishes the commit record, so the step never blocks on D2H and
    the committed point a preemption recovery restarts from is always
    a fully-published snapshot.

    ``step()`` expects the GLOBAL batch (identical numpy on every
    process; jax places each process's addressable shard).  Returns the
    loss, or None once this worker is detached.
    """

    def __init__(self, loss_fn: Callable, optimizer, init_params,
                 poll_every: int = 1, recover_timeout: float = 60.0,
                 snapshot_every=1):
        import jax
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.poll_every = max(1, int(poll_every))
        self.recover_timeout = recover_timeout
        # commit (device->host snapshot) cadence: recovery redoes at most
        # snapshot_every steps from the last committed state; 1 = commit
        # every step — fine for small models, ruinous at model scale
        # (a 470M params+adam state is 5.3 GB to move device->host per
        # commit; not measured on the current machine).  "auto" derives
        # the cadence from the FIRST measured step + commit: the
        # smallest cadence whose amortized commit cost is under
        # KFT_SNAPSHOT_BUDGET (default 5%) of the step — trading
        # recovery redo distance for throughput explicitly.
        self._auto_snap = snapshot_every == "auto"
        self.snapshot_every = (1 if self._auto_snap
                               else max(1, int(snapshot_every)))
        self._auto_commit_s = 0.0  # measured at step 1 in auto mode; a
        # joiner restored into an auto run may derive with 0 — the
        # cadence allreduce-MAX adopts the survivors' real value
        self._auto_join_s = 0.0  # async tail of the measured commit
        self._last_step_s: Optional[float] = None
        # kfsnap: commits are initiated by step() and finished (join +
        # publish) on this background committer — step() never blocks
        # on the device->host transfer (elastic/snapshot.py)
        self._committer = AsyncCommitter()
        self.we = E.from_env()
        if self.we.singleton:
            raise RuntimeError(
                "DistributedElasticTrainer needs the launcher env ABI "
                "(KFT_*); for single-process elastic use ElasticTrainer")
        D.require_own_chips(list(self.we.peers), self.we.rank())
        self.trained_samples = 0
        self.step_count = 0
        self._round = 0  # per-version fence round
        # persistent XLA cache before the first compile below: a
        # respawned or regrown worker deserialises its programs
        from ..utils.compile_cache import enable_compile_cache
        enable_compile_cache()
        # host-state init BEFORE joining any plane: it triggers this
        # process's first jax compilations, and a fresh joiner doing
        # them AFTER the rendezvous stalls warmed-up survivors past
        # their host-plane recv timeout (the first thing the sharded
        # sync does is RECEIVE from the joiner)
        self._init_state(init_params)
        self._committed_progress = (0, 0)
        # kfguard liveness lease: pumped from step() so a HUNG step loop
        # stops renewing and the watcher escalates (elastic/heartbeat.py);
        # registered before the first compile so /health shows the worker
        # from birth
        from .heartbeat import HeartbeatSender
        self._heartbeat = HeartbeatSender.from_env(self.we)
        if self._heartbeat is not None:
            self._heartbeat.beat(rank=self.we.rank(), step=0,
                                 version=self.we.cluster_version)
        self.peer = native.default_peer()
        self.version = self.peer.token
        self._last_seen_version = self.version
        D.reinit(self.peer.peers, self.peer.rank, self.version,
                 local_device_ids=self.we.chip_ids)
        self._sync_state()
        self._build()

    # ------------------------------------------------------------ internals
    def _init_state(self, init_params) -> None:
        """Host-side initial state, before any device state exists; the
        sharded sibling overrides this (it never materialises full
        optimizer state on one host)."""
        import jax
        self._host_params = jax.tree_util.tree_map(np.asarray, init_params)
        # host-side optimizer init so a snapshot exists before any device
        # state does; new joiners overwrite it via the rank-0 broadcast
        self._host_opt = jax.tree_util.tree_map(
            np.asarray, self.optimizer.init(self._host_params))

    def _sync_state(self) -> None:
        """Adopt rank 0's committed state AND the progress counters that
        describe it (reference: state broadcast on every membership
        change, experimental/hook/elastic.py:62-84).  Counters ride the
        same broadcast as the state — a MAX of counters could count a
        step whose update came from a rank that never committed it,
        silently skipping data; rank 0's (state, counters) pair is
        always consistent."""
        from ..monitor import net as _net
        _chaos_point("elastic.sync_state.begin", rank=self.peer.rank,
                     step=self.step_count, version=self.version)
        with _trace_span("elastic.sync_state", category="elastic",
                         rank=self.peer.rank, step=self.step_count,
                         version=self.version), \
                _net.Transfer("resize.sync",
                              direction=("egress" if self.peer.rank == 0
                                         else "ingress"),
                              rank=self.peer.rank,
                              version=self.version) as xf:
            with xf.phase("wire"):
                self._sync_state_inner()
            xf.add(_net.tree_bytes(self._host_params)
                   + _net.tree_bytes(self._host_opt))

    def _sync_state_inner(self) -> None:
        self._host_params = D.broadcast_host_tree(
            self._host_params, self.peer, root=0,
            name=f"params@{self.version}")
        self._host_opt = D.broadcast_host_tree(
            self._host_opt, self.peer, root=0,
            name=f"opt@{self.version}")
        if self.peer.size > 1:
            got = self.peer.broadcast(
                np.asarray([*self._committed_progress,
                            self.snapshot_every,
                            1 if self._auto_snap else 0], np.int64),
                root=0, name=f"progress@{self.version}")
            self._committed_progress = (int(got[0]), int(got[1]))
            # the commit cadence gates COLLECTIVE commits: a joiner
            # must adopt the membership's cadence (and whether auto
            # derivation is still pending), or its commit barriers
            # would have no partner
            self.snapshot_every = max(1, int(got[2]))
            self._auto_snap = bool(got[3])
        self.trained_samples, self.step_count = self._committed_progress

    def _build(self) -> None:
        """(Re)build mesh + jitted step over the CURRENT global device
        set and restore device state from the host snapshot."""
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import (Mesh, NamedSharding,
                                  PartitionSpec as P)
        devs = jax.devices()
        self.mesh = Mesh(np.array(devs), ("dp",))
        rep = NamedSharding(self.mesh, P())
        self._params = jax.device_put(
            jax.tree_util.tree_map(jnp.asarray, self._host_params), rep)
        self._opt = jax.device_put(
            jax.tree_util.tree_map(jnp.asarray, self._host_opt), rep)
        loss_fn, opt = self.loss_fn, self.optimizer

        def body(p, s, b):
            loss, grads = jax.value_and_grad(loss_fn)(p, b)
            grads = jax.lax.pmean(grads, "dp")
            loss = jax.lax.pmean(loss, "dp")
            updates, s = opt.update(grads, s, p)
            return optax.apply_updates(p, updates), s, loss

        self._step = jax.jit(jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(P(), P(), P("dp")), out_specs=(P(), P(), P())))
        self._batch_sharding = NamedSharding(self.mesh, P("dp"))
        # kfprof: the flops/HBM gauges follow the CURRENT program — each
        # (re)build re-arms the one-shot cost analysis, so elastic
        # resizes re-publish (monitor/profiler.py)
        self._cost_published = False

    def _fetch_version(self) -> int:
        if not self.we.config_server:
            return self.version
        try:
            v, _ = fetch_config(self.we.config_server, timeout=5.0)
            return v
        except (OSError, ValueError, KeyError):
            # transient config-server failure: poll again next step with
            # the last version — a resize is only ever DELAYED by this
            return self._last_seen_version

    def _rebuild_at(self, peer) -> None:
        _chaos_point("elastic.rebuild.begin", rank=peer.rank,
                     step=self.step_count, version=peer.token)
        with _trace_span("elastic.rebuild", category="elastic",
                         rank=peer.rank, step=self.step_count,
                         version=peer.token,
                         attrs={"size": peer.size}):
            self.peer = peer
            self.version = peer.token
            self._last_seen_version = max(self._last_seen_version,
                                          self.version)
            # fence rounds restart at every membership version: a freshly
            # joined worker counts from 0, so survivors must too
            # (collective names must match across the new membership)
            self._round = 0
            D.reinit(peer.peers, peer.rank, peer.token,
                     local_device_ids=self.we.chip_ids)
            self._sync_state()
            self._build()

    def _teardown_plane_ordered(self) -> None:
        """Take the LIVE data plane down while the old membership is
        still intact: non-coordinators disconnect first, the coordinator
        stops its service last — a client whose coordination service
        vanished mid-disconnect terminates the process (client.h
        fatal), which would turn a voluntary resize into a crash.  The
        sequencing rides the native host plane."""
        if not D.is_initialized():
            return
        p = self.peer
        _chaos_point("elastic.teardown.begin",
                     rank=None if p is None else p.rank,
                     step=self.step_count, version=self.version)
        with _trace_span("elastic.teardown", category="elastic",
                         rank=None if p is None else p.rank,
                         step=self.step_count, version=self.version):
            self._teardown_inner(p)

    def _teardown_inner(self, p) -> None:
        try:
            if p is not None and p.size > 1:
                p.barrier(name=f"plane-down@{self.version}")
                if p.rank == 0:
                    # wait until every client has disconnected, then
                    # stop the coordination service
                    p.barrier(name=f"plane-drained@{self.version}")
                    D.shutdown()
                else:
                    D.shutdown()
                    p.barrier(name=f"plane-drained@{self.version}")
                return
        except native.NativeError:
            pass  # a peer died mid-teardown: fall through to force
        D.shutdown()

    def _commit(self) -> None:
        """INITIATE a snapshot of device state + the counters describing
        it — the point a recovery or resize restarts from.

        kfsnap pipeline: this dispatches every leaf's
        ``copy_to_host_async`` (all transfers overlap) and returns; the
        background committer joins and then publishes host state and
        progress ATOMICALLY (state first, counters last), so
        ``_committed_progress`` never points at a torn snapshot — a
        death between dispatch and publish recovers from the previous
        durable commit (kfchaos ``snapshot.commit``).  Callers that
        need the commit durable NOW follow with :meth:`_commit_drain`.
        """
        _chaos_point("elastic.commit.begin", rank=self.peer.rank,
                     step=self.step_count, version=self.version)
        progress = (self.trained_samples, self.step_count)

        def publish(host) -> None:
            # runs on the committer thread: install the host state
            # BEFORE the progress record (each assignment is atomic
            # under the GIL; readers drain first anyway)
            self._host_params, self._host_opt = host
            self._committed_progress = progress

        with _trace_span("elastic.commit", category="elastic",
                         rank=self.peer.rank, step=self.step_count,
                         version=self.version):
            self._committer.initiate((self._params, self._opt), publish,
                                     rank=self.peer.rank,
                                     step=self.step_count,
                                     version=self.version)

    def _commit_drain(self) -> None:
        """Block until the last initiated commit is durable (published).
        No-op for the sharded sibling, whose commit is a synchronous
        collective.  Re-raises a failed in-flight commit; the previous
        published commit stands."""
        self._committer.drain()

    def _drain_quietly(self, where: str) -> None:
        """Drain on a path that must proceed regardless (recovery,
        shutdown): a failed in-flight commit is logged, not fatal —
        the previous durable commit is the recovery point."""
        import sys
        try:
            self._commit_drain()
        except Exception as e:
            print(f"kft: in-flight commit abandoned at {where}: {e!r}",
                  file=sys.stderr)

    def _measure_commit(self) -> None:
        """One fully-drained commit, split into the BLOCKING cost the
        step pays (kfsnap dispatch; the whole commit when commits are
        synchronous) and the async join tail — the two inputs of the
        auto-cadence derivation."""
        import time as _time
        t0 = _time.perf_counter()
        self._commit()
        self._auto_commit_s = _time.perf_counter() - t0
        self._commit_drain()
        self._auto_join_s = (_time.perf_counter() - t0
                             - self._auto_commit_s)

    def _pre_teardown(self) -> None:
        """Hook between the pre-resize commit and the plane teardown,
        while the OLD membership is still fully alive.  The sharded
        sibling hands departing workers' state shards to survivors here;
        replicated DP needs nothing (every process holds everything)."""

    def _resize(self) -> bool:
        """Apply a pending config change; False when detached."""
        _chaos_point("elastic.resize.begin", rank=self.peer.rank,
                     step=self.step_count, version=self.version)
        import time as _time
        _t0 = _time.perf_counter()
        with _trace_span("elastic.resize", category="elastic",
                         rank=self.peer.rank, step=self.step_count,
                         version=self.version) as _sp:
            # everyone is at the same fence: commit the live device state
            # so a voluntary resize never discards steps since the last
            # snapshot.  The commit must be DURABLE before the plane
            # comes down — the post-rebuild state broadcast reads the
            # published host snapshot — so this is a drain point.
            self._commit()
            self._commit_drain()
            self._pre_teardown()
            # the old plane comes down FIRST, with everyone still alive —
            # after resize_from_url the old host membership no longer
            # exists to sequence the teardown
            self._teardown_plane_ordered()
            changed, detach = native.resize_from_url()
            if detach:
                _trace_event("elastic.detach", category="elastic",
                             step=self.step_count, version=self.version)
                return False
            self._rebuild_at(native.installed_peer())
            if _sp is not None:
                _sp.set(new_size=self.peer.size)
        from ..monitor import get_monitor
        get_monitor().observe("kungfu_tpu_resize_seconds",
                              _time.perf_counter() - _t0)
        return True

    def _recover(self, batch, cause=None) -> Optional[float]:
        """A peer died mid-protocol: tear down the data plane, absorb the
        shrink over the host plane, rebuild, and REDO the interrupted
        step(s) from the last committed snapshot."""
        D.shutdown()
        # settle the commit pipeline before rebuilding: _sync_state
        # broadcasts the PUBLISHED host snapshot, so an in-flight commit
        # must either land or be abandoned (previous commit stands)
        self._drain_quietly("recovery")
        _trace_event("elastic.recover.begin", category="elastic",
                     step=self.step_count, version=self.version,
                     attrs={"cause": type(cause).__name__ if cause else None})
        try:
            peer = native.recover_from_failure(timeout=self.recover_timeout)
        except native.NativeError as e:
            # not a membership event after all: surface the original
            # failure instead of a bare recovery timeout
            raise e from cause
        if peer is None:
            return None  # this worker was shrunk away
        self._rebuild_at(peer)
        return self.step(batch)

    # ---------------------------------------------------------------- public
    def step(self, global_batch) -> Optional[float]:
        """One fenced, elastic training step; None once detached."""
        import jax
        import time as _time
        if _flags.is_detached():
            return None
        # straggler-attributable timing for the cluster metrics plane
        # (monitor/doctor.py): a rank's OWN step time is the wall time
        # minus what it spent WAITING at the version fence.  A slow rank
        # carries its slowness in own-time; its peers carry it in fence
        # wait — so kungfu_tpu_step_seconds skew names the straggler and
        # collective_seconds{name="step_fence"} feeds the interference
        # detector instead of smearing one rank's stall over everyone.
        _t_entry = _time.perf_counter()
        _fence_wait = 0.0
        if self._heartbeat is not None:
            # lease renewal rides the step path BY DESIGN: a wedged
            # step loop must stop beating (see elastic/heartbeat.py)
            self._heartbeat.beat(rank=self.peer.rank,
                                 step=self.step_count,
                                 version=self.version)
        _chaos_point("elastic.step.fence", rank=self.peer.rank,
                     step=self.step_count, version=self.version)
        while True:
            local = (self._fetch_version()
                     if self.step_count % self.poll_every == 0
                     else self._last_seen_version)
            self._last_seen_version = max(self._last_seen_version, local)
            _t_fence = _time.perf_counter()
            try:
                agreed = int(self.peer.all_reduce(
                    np.asarray([self._last_seen_version], np.int64),
                    op="MAX",
                    name=f"fence@{self.version}:{self._round}")[0])
            except native.NativeError as e:
                return self._recover(global_batch, cause=e)
            _fence_wait += _time.perf_counter() - _t_fence
            self._round += 1
            self._last_seen_version = max(self._last_seen_version, agreed)
            if agreed <= self.version:
                break
            try:
                if not self._resize():
                    return None
            except (native.NativeError, OSError) as e:
                # a peer died DURING the voluntary resize (handoff
                # barrier, post-rebuild commit, ...) or the config
                # server dropped out mid-resize (OSError from the
                # resize fetch): absorb either through the same
                # recovery path as a mid-step death — its poll loop
                # retries the config server until the membership
                # resolves
                return self._recover(global_batch, cause=e)
            # re-fence on the NEW membership before stepping: a freshly
            # joined worker's first fence must pair with everyone's
        try:
            _t0 = _time.perf_counter()
            batch = jax.device_put(global_batch, self._batch_sharding)
            _chaos_point("elastic.step.compute", rank=self.peer.rank,
                         step=self.step_count, version=self.version)
            params, opt, loss = self._step(self._params, self._opt, batch)
            lossv = float(np.asarray(loss))  # blocks until the step ran
            self._last_step_s = _time.perf_counter() - _t0
        except (native.NativeError, RuntimeError, OSError) as e:
            # RuntimeError covers XlaRuntimeError (a dead peer inside a
            # compiled collective); deterministic user errors (shape /
            # dtype / tracing TypeError|ValueError) propagate instead of
            # being misread as membership failures
            if _flags.is_detached():
                raise
            return self._recover(global_batch, cause=e)
        self._params, self._opt = params, opt
        from ..monitor import get_monitor
        _mon = get_monitor()
        _mon.observe("kungfu_tpu_step_seconds",
                     _time.perf_counter() - _t_entry - _fence_wait)
        if _fence_wait > 0:
            _mon.observe("kungfu_tpu_collective_seconds", _fence_wait,
                         labels={"name": "step_fence"})
        self.step_count += 1
        leaf = jax.tree_util.tree_leaves(global_batch)[0]
        self.trained_samples += int(leaf.shape[0])
        if self._auto_snap and self.step_count == 1:
            # measure ONE commit now (a snapshot must exist early
            # anyway); the cadence itself is derived at step 2, whose
            # step time is compile-free — deriving from the
            # compile-inflated first step would underestimate the
            # cadence by the compile/step ratio
            try:
                _t_commit = _time.perf_counter()
                self._measure_commit()
                _commit_s = _time.perf_counter() - _t_commit
            except native.NativeError as e:
                return self._recover(global_batch, cause=e)
            self._publish_step_phases(
                _time.perf_counter() - _t_entry, _fence_wait,
                _commit_s, batch)
            return lossv
        if self._auto_snap and self.step_count >= 2:
            budget = _snapshot_budget()
            step_s = max(self._last_step_s or 1e-3, 1e-3)
            # 0 = "I never measured a commit" (a joiner restored after
            # the step-1 measurement); the MAX then adopts whichever
            # member did measure.  Two constraints: the BLOCKING cost
            # (the kfsnap dispatch; the full commit for the sharded
            # sibling's synchronous collective) amortizes under the
            # budget, and the async join tail fits inside the cadence
            # window so commits never queue behind each other.
            cadence = (0 if self._auto_commit_s == 0.0 else
                       max(1,
                           int(np.ceil(self._auto_commit_s
                                       / (budget * step_s))),
                           int(np.ceil(self._auto_join_s / step_s))))
            # the cadence gates COLLECTIVE commits: every process must
            # adopt the same one, not its locally-measured one
            if self.peer.size > 1:
                try:
                    cadence = int(self.peer.all_reduce(
                        np.asarray([cadence], np.int64), op="MAX",
                        name=f"snapcadence@{self.version}:{self.step_count}"
                    )[0])
                except native.NativeError as e:
                    return self._recover(global_batch, cause=e)
            if cadence == 0:
                # NO current member measured (every survivor joined
                # after step 1): measure one collective commit together
                # now and derive at the next step
                try:
                    _t_commit = _time.perf_counter()
                    self._measure_commit()
                    _commit_s = _time.perf_counter() - _t_commit
                except native.NativeError as e:
                    return self._recover(global_batch, cause=e)
                self._publish_step_phases(
                    _time.perf_counter() - _t_entry, _fence_wait,
                    _commit_s, batch)
                return lossv
            self.snapshot_every = cadence
            self._auto_snap = False
            if self.snapshot_every > 1 and self.peer.rank == 0:
                import sys as _sys
                print(f"kft: snapshot_every=auto -> {self.snapshot_every}"
                      f" (commit {self._auto_commit_s:.2f}s vs step "
                      f"{step_s:.3f}s, budget {budget:.0%})",
                      file=_sys.stderr)
        _commit_s = 0.0
        if self.step_count % self.snapshot_every == 0:
            try:
                _t_commit = _time.perf_counter()
                self._commit()
                _commit_s = _time.perf_counter() - _t_commit
            except native.NativeError as e:
                # sharded commits ride the host plane (shard-replica
                # exchange); a peer death there is a membership event
                # like any other — an INCOMPLETE commit is never
                # recorded, so recovery restarts from the previous one
                return self._recover(global_batch, cause=e)
        self._publish_step_phases(_time.perf_counter() - _t_entry,
                                  _fence_wait, _commit_s, batch)
        return lossv

    def _publish_step_phases(self, wall_s, fence_wait, commit_s,
                             batch) -> None:
        """kfprof device-time attribution for the step that just ran
        (monitor/profiler.py): the measured compute (dispatch->sync
        around the jitted call), collective (version-fence wait) and
        transfer (kfsnap commit dispatch) splits, with host as the
        remainder; plus the one-shot compiled-cost gauges after each
        (re)build and the per-step roofline fraction."""
        from ..monitor import profiler as _prof
        phases = getattr(self, "_phases", None)
        if phases is None:
            phases = self._phases = _prof.StepPhases(loop="train")
        phases.add("compute", self._last_step_s or 0.0)
        phases.add("collective", fence_wait)
        phases.add("transfer", commit_s)
        phases.publish(wall_s, rank=self.peer.rank, step=self.step_count,
                       version=self.version)
        if not getattr(self, "_cost_published", True):
            # after the flag flips the cost is settled until the next
            # _build; set first so a failing analysis is not retried
            # every step
            self._cost_published = True
            _prof.publish_compiled_cost(self._step, self._params,
                                        self._opt, batch)
        _prof.publish_roofline(self._last_step_s or 0.0)

    @property
    def size(self) -> int:
        return self.peer.size

    @property
    def rank(self) -> int:
        return self.peer.rank

    def num_devices(self) -> int:
        import jax
        return len(jax.devices())

    def current_params(self):
        self._commit_drain()  # surface the newest durable commit
        return self._host_params

    def shutdown(self) -> None:
        """Ordered end-of-job teardown (all members should call it)."""
        if self._heartbeat is not None:
            self._heartbeat.stop()
        self._drain_quietly("shutdown")
        self._teardown_plane_ordered()
        self._committer.close()

    def propose_new_size(self, n: int) -> bool:
        """Rank-0 convenience: PUT a resized cluster to the config server
        (reference ProposeNewSize, peer/legacy.go:18-38); every member
        picks it up at its next step fence."""
        import kungfu_tpu as kft
        return kft.propose_new_size(n)
