"""Pair averaging (AD-PSGD family) — decentralised model exchange.

Reference: srcs/python/kungfu/tensorflow/optimizers/async_sgd.py:13-142 —
each peer requests the model of one *other* peer each step and averages:
``v <- 0.5 * (v + v_peer)``, then applies its local gradient.  The
reference picks peers randomly/round-robin via an asynchronous p2p store.

TPU-native redesign: asynchronous point-to-point pulls do not exist inside
an XLA program, so the pairing becomes a *scheduled* collective_permute:
step t exchanges with the peer at distance ``2^(t mod ceil(log2 n))`` —
hypercube gossip.  Every peer both sends and receives exactly one model
per step; one cycle of the ceil(log2 n) shifts spreads every lane's value
to all n lanes (any distance has a binary expansion), so variance
contracts per cycle while the compiled program holds only log2(n)
ppermute branches (a shift-per-peer round-robin was O(n^2) program text
at 256 lanes).  This preserves AD-PSGD's gossip mixing (doubly-stochastic
averaging matrix per step) while riding ICI at full bandwidth.  The
deviation from true asynchrony is documented: there is no stale-model
window; the mixing schedule is deterministic and a lane directly meets
ceil(log2 n) distinct partners per cycle (indirect mixing covers the
rest).  The TRUE-asynchronous store-backed variant for multi-controller
setups is :class:`AsyncPairAverager` below (native p2p store,
random/roundrobin peer selection, optional prefetch double-buffer).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax

from ..comm.mesh import PEER_AXIS


class AsyncPairAverager:
    """TRUE-asynchronous AD-PSGD model exchange over the host runtime's
    p2p store — the multi-controller companion to :func:`pair_averaging`
    (reference: PairAveragingOptimizer, async_sgd.py:13-142, over the Go
    store; selection strategies random/roundrobin, peer_to_peer.cpp
    SelectionStrategy).

    Each controller trains independently; per step it requests one OTHER
    peer's latest saved model (no synchronization — the serving peer's
    store answers from whatever version it last saved), mixes
    ``v <- (1-mix)*v + mix*v_peer``, and saves its own model back.

    Usage (inside a launcher-spawned worker holding a NativePeer)::

        avg = AsyncPairAverager(native.default_peer())
        avg.save(params)               # step-0 init (reference: barrier'd)
        ...
        params = avg.mix(params)       # request + average, then train
        avg.save(params)
    """

    def __init__(self, peer, selection: str = "random", mix: float = 0.5,
                 name: str = "model", seed: Optional[int] = None,
                 prefetch: bool = False):
        import numpy as np

        from ..plan.mst import RoundRobin
        self._peer = peer
        self._mix = float(mix)
        self._name = name
        self._prefetch = bool(prefetch)
        self._inflight = None  # Future pulling the NEXT peer's model
        # persistent pull destinations: a FRESH model-size numpy buffer
        # per exchange makes the kernel re-fault + zero-fill the whole
        # mapping every pull — measured 0.6-1.5 vs 3.2 GiB/s at 1 GB on
        # loopback (native.request docstring).  The async prefetch gets
        # its OWN two-slot rotation (a prefetch in flight must never
        # share the buffer the current mix is reading) and the sync
        # path its own single slot — sharing slots across the two paths
        # could hand a sync pull the buffer an in-flight prefetch is
        # still writing
        self._bufs = [None, None]
        self._buf_i = 0
        self._sync_buf = None
        self._mask = [r != peer.rank for r in range(peer.size)]
        if selection == "roundrobin":
            rr = RoundRobin()
            self._pick = lambda: rr(self._mask)
        elif selection == "random":
            rng = np.random.RandomState(
                peer.rank if seed is None else seed)
            others = [r for r in range(peer.size) if r != peer.rank]
            self._pick = (lambda: int(rng.choice(others))) if others else (
                lambda: -1)
        else:
            raise ValueError(f"unknown selection {selection!r}")

    _unravel = None

    def _flat(self, tree):
        """Model pytree -> contiguous f32-ish numpy vector.

        All-numpy trees take a pure-numpy path: routing host-resident
        models through jax's ravel_pytree would stage them onto the
        accelerator and fetch them back, a round trip the exchange
        itself does not need.  Device trees still use ravel_pytree (the D2H staging is
        then inherent, as in the reference's GPU path)."""
        import numpy as np
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if leaves and all(isinstance(l, np.ndarray) for l in leaves):
            metas = [(l.shape, l.dtype, int(l.size)) for l in leaves]

            def unravel(flat):
                out, off = [], 0
                for shape, dt, sz in metas:
                    out.append(np.asarray(flat[off:off + sz],
                                          dtype=dt).reshape(shape))
                    off += sz
                return jax.tree_util.tree_unflatten(treedef, out)

            self._unravel = unravel
            return np.concatenate([np.ravel(l) for l in leaves])
        from jax.flatten_util import ravel_pytree
        flat, unravel = ravel_pytree(tree)
        self._unravel = unravel  # same treedef every step: cache it
        return np.asarray(flat)

    def save(self, tree, version: int = -1) -> None:
        """Publish this controller's model to its store."""
        self._peer.save(self._name, self._flat(tree), version=version)

    def _dst(self, like):
        import numpy as np
        i = self._buf_i
        self._buf_i = 1 - i
        if self._bufs[i] is None or self._bufs[i].nbytes != like.nbytes:
            self._bufs[i] = np.empty_like(like)
        return self._bufs[i]

    def _mix_flat(self, flat, version):
        import numpy as np
        target = self._pick()
        if target < 0:
            return flat
        if (self._sync_buf is None
                or self._sync_buf.nbytes != flat.nbytes):
            self._sync_buf = np.empty_like(flat)
        theirs = self._peer.request(target, self._name, flat,
                                    version=version,
                                    out=self._sync_buf)
        return (1.0 - self._mix) * flat + self._mix * theirs

    def mix(self, tree, version: int = -1):
        """Pull one peer's model and average it into ``tree``."""
        mixed = self._mix_flat(self._flat(tree), version)
        return self._unravel(mixed)

    def mix_and_save(self, tree, version: int = -1):
        """``mix`` then ``save`` with a single flatten of the model —
        the per-step fast path.

        With ``prefetch=True`` the peer model consumed here was pulled
        DURING the preceding local step (double buffer — the reference's
        AsyncRequestModel prefetch, peer_to_peer.cpp:8-524): after
        mixing, the next pull is issued immediately so it overlaps the
        caller's next compute instead of stalling the loop."""
        flat = self._flat(tree)
        if not self._prefetch:
            mixed = self._mix_flat(flat, version)
            self._peer.save(self._name, mixed, version=version)
            return self._unravel(mixed)
        if version != -1:
            # the in-flight pull was issued during the PREVIOUS step and
            # can only ask for the peer's LATEST model; an explicit
            # version would silently bind to the prior step's number
            raise ValueError("prefetch mode exchanges latest models "
                             "(version=-1); use prefetch=False for "
                             "explicit-version pulls")
        if self._inflight is None:  # cold start: no overlap this once
            self._start_prefetch(flat)
        inflight, self._inflight = self._inflight, None
        theirs = None
        if inflight is not None:
            try:
                theirs = inflight.result()
            except Exception as e:  # peer died/fenced: skip this round's
                # mix rather than wedging on a cached exception forever
                import sys
                print(f"kft: pair-averaging prefetch failed ({e}); "
                      f"skipping this round's mix", file=sys.stderr)
        mixed = flat if theirs is None else (
            (1.0 - self._mix) * flat + self._mix * theirs)
        self._peer.save(self._name, mixed, version=version)
        self._start_prefetch(mixed)
        return self._unravel(mixed)

    def _start_prefetch(self, like, version: int = -1) -> None:
        target = self._pick()
        self._inflight = (self._peer.request_async(
            target, self._name, like, version=version,
            out=self._dst(like))
            if target >= 0 else None)


def pair_averaging(base: optax.GradientTransformation,
                   n: int,
                   axis_name: str = PEER_AXIS,
                   mix: float = 0.5
                   ) -> optax.GradientTransformation:
    """PairAveragingOptimizer equivalent for an ``n``-lane mesh.

    ``n`` must be the static mesh size (collective permutations are
    compile-time constants under XLA).
    """
    if n < 1:
        raise ValueError("n must be >= 1")

    def init_fn(params):
        return {"base": base.init(params), "step": jnp.zeros((), jnp.int32)}

    def update_fn(updates, state, params=None):
        if params is None:
            raise ValueError("pair_averaging requires params")
        step = state["step"]
        local_updates, base_state = base.update(updates, state["base"], params)
        if n == 1:
            return local_updates, {"base": base_state, "step": step + 1}
        # POWER-OF-TWO shift schedule: step t exchanges with the peer at
        # distance 2^(t mod ceil(log2 n)) — hypercube gossip.  Each round
        # applies the doubly-stochastic W_s = (1-mix)I + mix*P_s, and one
        # full cycle of the log2(n) shifts spreads every lane's value to
        # all n lanes (any distance has a binary expansion), so variance
        # contracts per cycle just like the n-1-shift round-robin — but
        # the compiled program holds ceil(log2 n) ppermute branches
        # instead of n-1 (255 branches at 256 lanes was O(n^2) program
        # text in perm entries; this is O(n log n)).
        import math
        k = max(1, math.ceil(math.log2(n)))
        branches = []
        for j in range(k):
            s = (2 ** j) % n
            perm = [(i, (i + s) % n) for i in range(n)]

            def make(perm):
                def f(p):
                    return jax.tree_util.tree_map(
                        lambda t: lax.ppermute(t, axis_name, perm=perm), p)
                return f
            branches.append(make(perm))
        peer_params = lax.switch(step % k, branches, params)
        pull = jax.tree_util.tree_map(lambda q, p: mix * (q - p),
                                      peer_params, params)
        merged = jax.tree_util.tree_map(lambda u, d: u + d, local_updates, pull)
        return merged, {"base": base_state, "step": step + 1}

    return optax.GradientTransformation(init_fn, update_fn)
