"""Elastic training: runtime cluster resize with state re-synchronisation.

Reference protocol (srcs/go/kungfu/peer/peer.go:227-263 + experimental/
hook/elastic.py:50-113): rank 0 proposes a resized cluster to the config
server; all peers poll until consensus on the cluster digest; every peer
rebuilds its session with a bumped version token; removed peers see
``detached`` and stop; survivors sync progress (allreduce-max of trained
samples) and broadcast model state to newcomers.

TPU-native mapping: the "cluster" is the set of mesh lanes.  A resize tears
down the mesh, re-lays replicas on the first ``n`` devices, and recompiles
the step (XLA programs are fixed-shape — SURVEY §7 "hard parts").  Compiled
steps are cached per size, so oscillating schedules (4→8→4…) recompile only
once per distinct size.  Version tokens fence stale state exactly like the
reference's connection tokens.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Optional, Tuple

import jax
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..comm.mesh import flat_mesh
from ..comm.session import Session
from ..plan.cluster import Cluster
from ..plan.peer import PeerID, PeerList
from ..plan.topology import Strategy
from ..training import build_train_step, build_train_step_with_state
from . import state as _flags
from .config_server import fetch_config
from .snapshot import snapshot as _snapshot


def _restack(host_tree, n_new: int, mesh):
    """Re-lay host replicas onto a new mesh: survivors keep their replica,
    newcomers clone lane 0 (the reference's broadcast-from-rank-0 sync).
    The grow case stages through the kffast buffer pool: repeated
    resizes recycle one host staging buffer per (dtype, nbytes) class
    instead of fresh-allocating the full host tree each time (the pool
    reuses a slot only once nothing references it, device_put
    included)."""
    from ..store.pool import default_pool
    spec = P(mesh.axis_names)

    def re(t):
        t = np.asarray(t)
        n_old = t.shape[0]
        if n_new <= n_old:
            out = t[:n_new]
        else:
            out = default_pool().take(t.dtype, (n_new,) + t.shape[1:])
            out[:n_old] = t
            out[n_old:] = t[0:1]
        # host array + sharding: each lane's slice goes straight to its
        # device (jnp.asarray first would stage all n lanes on device 0)
        return jax.device_put(out, NamedSharding(mesh, spec))
    return jax.tree_util.tree_map(re, host_tree)


class ElasticTrainer:
    """Drives elastic distributed training over a resizable mesh.

    ``optimizer_factory(n)`` builds the optimizer for an ``n``-lane cluster
    (pair averaging needs the static lane count).
    """

    def __init__(self,
                 loss_fn: Callable,
                 optimizer_factory: Callable[[int], optax.GradientTransformation],
                 init_params,
                 init_size: Optional[int] = None,
                 config_server_url: Optional[str] = None,
                 max_size: Optional[int] = None,
                 init_model_state=None):
        """``init_model_state`` switches on non-trained model state
        (BatchNorm running stats): ``loss_fn(params, model_state, batch) ->
        (loss, new_model_state)`` and the state rides every resize /
        checkpoint alongside the params (the reference broadcasts BN stats
        with the rest of the variables on sync points —
        experimental/hook/elastic.py:62-84)."""
        self.loss_fn = loss_fn
        self.optimizer_factory = optimizer_factory
        self.config_server_url = config_server_url
        total = len(jax.devices())
        self.max_size = max_size or total
        self.n = init_size or total
        self.version = 0          # local session/membership version
        self.config_version = -1  # last applied config-server version
        self.trained_samples = 0
        self.step_count = 0
        # resize-cost instrumentation (SURVEY §7 names the recompile as
        # the dominant elastic risk; these let callers measure it)
        self.last_resize_seconds: Optional[float] = None
        self.last_resize_compiled = False  # True: new step fn was built
        # persistent XLA cache: a respawned/grown worker pays a disk
        # deserialisation instead of a recompile
        from ..utils.compile_cache import enable_compile_cache
        enable_compile_cache()
        stack = lambda tree: jax.tree_util.tree_map(
            lambda t: np.broadcast_to(np.asarray(t)[None],
                                      (self.n,) + np.asarray(t).shape).copy(),
            tree)
        self.has_model_state = init_model_state is not None
        self._host_params = stack(init_params)
        self._host_mstate = (stack(init_model_state)
                             if self.has_model_state else None)
        self._step_cache: Dict[int, Callable] = {}
        self._install(self.n, fresh_opt=True)

    # ------------------------------------------------------------------ core
    def _install(self, n: int, fresh_opt: bool) -> None:
        self.mesh = flat_mesh(n=n)
        self.session = Session(mesh=self.mesh, version=self.version)
        self.optimizer = self.optimizer_factory(n)
        self.params = _restack(self._host_params, n, self.mesh)
        if self.has_model_state:
            self.model_state = _restack(self._host_mstate, n, self.mesh)
        if fresh_opt:
            from ..training import init_opt_state
            self.opt_state = init_opt_state(self.optimizer, self.params,
                                            self.mesh)
        if n not in self._step_cache:
            build = (build_train_step_with_state if self.has_model_state
                     else build_train_step)
            # donation is safe here by construction: step() rebinds every
            # donated root in the call statement itself, and resize/snapshot
            # read self.params only via the synchronous kfsnap path.  The
            # kfcheck use-after-donate pass gates this — any new post-call
            # read of a donated buffer turns CI step 0 red.
            self._step_cache[n] = build(self.loss_fn, self.optimizer,
                                        self.mesh, donate=True)
        self._step = self._step_cache[n]
        self.n = n

    def step(self, global_batch) -> float:
        """One training step; batch leading axis sharded over current lanes."""
        if self.has_model_state:
            self.params, self.opt_state, self.model_state, loss = self._step(
                self.params, self.opt_state, self.model_state, global_batch)
        else:
            self.params, self.opt_state, loss = self._step(
                self.params, self.opt_state, global_batch)
        self.step_count += 1
        bs = jax.tree_util.tree_leaves(global_batch)[0].shape[0]
        self.trained_samples += int(bs)
        return float(np.asarray(loss)[0])

    # ---------------------------------------------------------------- resize
    def resize(self, new_size: int) -> bool:
        """Apply a new cluster size; returns True when membership changed.

        Follows the reference sequence: consensus fence → version bump →
        session rebuild → state re-sync (survivor replicas kept, newcomer
        lanes cloned from lane 0) → progress sync.
        """
        from ..trace import event as _trace_event, span as _trace_span
        from ..utils.trace import log_event
        if new_size == self.n:
            return False
        if new_size > self.max_size:
            raise ValueError(f"size {new_size} exceeds capacity {self.max_size}")
        if new_size <= 0:
            log_event(f"resize-detach:{self.n}->0")
            _trace_event("elastic.detach", category="elastic",
                         step=self.step_count, version=self.version)
            _flags.set_detached(True)
            return True
        # consensus fence on the proposal (trivially true single-controller,
        # real check under multi-controller)
        if not self.session.bytes_consensus(str(new_size).encode()):
            log_event(f"resize-abort:{self.n}->{new_size}")
            raise RuntimeError("resize proposal diverged across peers")
        # begin is logged after the fence so begin/end events always pair
        log_event(f"resize-begin:{self.n}->{new_size}")
        t0 = time.perf_counter()
        self.last_resize_compiled = new_size not in self._step_cache
        with _trace_span("elastic.resize", category="elastic",
                         step=self.step_count, version=self.version,
                         attrs={"from": self.n, "to": new_size}):
            # kfsnap: ONE dispatch fan-out over params + model state +
            # optimizer state, so every device->host transfer of the
            # pre-resize snapshot overlaps (elastic/snapshot.py)
            self._host_params, host_mstate, host_opt = _snapshot(
                (self.params,
                 self.model_state if self.has_model_state else None,
                 self.opt_state))
            if self.has_model_state:
                self._host_mstate = host_mstate
            self.version += 1
            _flags.bump_cluster_version()
            self._install(new_size, fresh_opt=False)
            self.opt_state = _restack(host_opt, new_size, self.mesh)
            self.session.barrier()
        # NOTE: jit compilation is lazy — the FIRST step at the new size
        # pays the (possibly cached) compile; measure resize cost as
        # last_resize_seconds + (first-step - steady-step) latency, as
        # benchmarks/resize_cost.py does
        self.last_resize_seconds = time.perf_counter() - t0
        from ..monitor import get_monitor
        get_monitor().observe("kungfu_tpu_resize_seconds",
                              self.last_resize_seconds)
        log_event(f"resize-end:{new_size}")
        log_event(f"resize-cost:{self.last_resize_seconds:.3f}s"
                  f"{':new-step-fn' if self.last_resize_compiled else ''}")
        return True

    def resize_from_url(self, timeout: float = 30.0) -> Tuple[bool, bool]:
        """Poll the config server and apply its cluster size.

        Returns (changed, detached) like the reference's
        resize_cluster_from_url op (ops/adapt.py:5-21).
        """
        if not self.config_server_url:
            raise ValueError("no config server configured")
        # kfguard rpc layer owns the retry loop: jittered backoff under
        # one overall deadline budget, then the REAL last error surfaces
        # (conn refused / 404-before-first-PUT / truncated JSON are all
        # retried; utils/rpc.py)
        version, cluster = fetch_config(self.config_server_url,
                                        deadline=timeout,
                                        retry_unseeded=True)
        if version == self.config_version:
            return False, False  # already applied this server config
        changed = self.resize(min(cluster.size(), self.max_size))
        self.config_version = version
        return changed, _flags.is_detached()

    # ------------------------------------------------------------- state sync
    def sync_progress(self) -> int:
        """Allreduce-max of trained samples (reference: elastic.py:62-84
        before_run sync); meaningful under multi-controller.

        The counter crosses the collective as exact int32 words (jax
        downcasts int64 to int32 without x64 mode, which would silently
        wrap past 2^31 samples; float32 would corrupt past 2^24).  Two
        max-rounds make the split lexicographically exact: first the high
        word, then the low word restricted to holders of the winning high
        word (elementwise max over both words at once could overshoot)."""
        hi, lo = divmod(self.trained_samples, 1 << 31)
        xhi = np.full((self.n, 1), hi, np.int32)
        ghi = int(np.asarray(self.session.all_reduce(xhi, op="MAX"))[0, 0])
        cand = lo if hi == ghi else -1
        xlo = np.full((self.n, 1), cand, np.int32)
        glo = int(np.asarray(self.session.all_reduce(xlo, op="MAX"))[0, 0])
        self.trained_samples = (ghi << 31) + glo
        return self.trained_samples

    def current_params(self, lane: int = 0):
        # kfsnap: dispatch every leaf's D2H before the first join,
        # then slice the requested lane off the host views
        return jax.tree_util.tree_map(lambda t: t[lane],
                                      _snapshot(self.params))

    def current_model_state(self, lane: int = 0):
        """One lane's non-trained model state (BN running stats) for eval."""
        if not self.has_model_state:
            raise ValueError("trainer was built without model state")
        return jax.tree_util.tree_map(lambda t: t[lane],
                                      _snapshot(self.model_state))

    # ------------------------------------------------------------ checkpoints
    def save_checkpoint(self, ckpt, force: bool = False) -> bool:
        """Write lane-0 model + optimizer state and progress counters.

        One replica is the checkpoint (kungfu_tpu.checkpoint conventions);
        under model-averaging schemes whose replicas diverge, lane 0 is
        the representative — as in the reference, where rank 0's state is
        what survives a membership change."""
        state = {
            "model": self.current_params(0),
            "opt": jax.tree_util.tree_map(
                lambda t: np.asarray(t[0]),  # 0-d stays ndarray
                _snapshot(self.opt_state)),
        }
        if self.has_model_state:
            state["mstate"] = self.current_model_state(0)
        meta = {"trained_samples": self.trained_samples,
                "step_count": self.step_count,
                "size": self.n}
        return ckpt.save(self.step_count, state, meta=meta, force=force)

    def restore_checkpoint(self, ckpt, step: Optional[int] = None) -> int:
        """Resume from disk at the CURRENT cluster size (which may differ
        from the size at save time): the restored replica is broadcast to
        every lane, progress counters are restored.  Returns the step."""
        # shape-only template (no device->host copy of the live state)
        lane_template = lambda tree: jax.tree_util.tree_map(
            lambda t: jax.ShapeDtypeStruct(t.shape[1:], t.dtype), tree)
        like = {"model": lane_template(self.params),
                "opt": lane_template(self.opt_state)}
        if self.has_model_state:
            like["mstate"] = lane_template(self.model_state)
        step, state, meta = ckpt.restore(like=like, step=step)
        one = lambda tree: jax.tree_util.tree_map(
            lambda t: np.asarray(t)[None], tree)
        params = _restack(one(state["model"]), self.n, self.mesh)
        opt_state = _restack(one(state["opt"]), self.n, self.mesh)
        mstate = (_restack(one(state["mstate"]), self.n, self.mesh)
                  if self.has_model_state else None)
        # assign only after all restacks succeeded (keeps the n-lane
        # invariant of _host_params if an incompatible checkpoint raises)
        self.params = params
        self.opt_state = opt_state
        self._host_params = _snapshot(self.params)
        if self.has_model_state:
            self.model_state = mstate
            self._host_mstate = _snapshot(self.model_state)
        if meta:
            self.trained_samples = int(meta.get("trained_samples", 0))
            self.step_count = int(meta.get("step_count", step))
        return step
