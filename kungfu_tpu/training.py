"""High-level distributed training: replicate, broadcast, build the step.

The TPU-native reading of the reference's worker model: each mesh lane
(device) owns a *model replica*, stored as a peer-stacked pytree — leading
axis = lane, sharded over the mesh.  On each device this costs exactly one
replica, like the reference's per-worker model.  Synchronous SGD keeps the
replicas bit-identical (gradient allreduce); SMA / pair averaging let them
diverge and mix them, exactly as the reference's worker-local models do.

Reference analogues: optimizer wrapping (optimizers/core.py:6-72),
BroadcastGlobalVariables initializer (initializer/__init__.py:13-100).
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .comm import collectives as C
from .comm.mesh import PEER_AXIS, flat_mesh


def _stack_spec(mesh: Mesh) -> P:
    return P(mesh.axis_names)


def replicate(params, mesh: Optional[Mesh] = None):
    """Stack one replica per lane and shard over the mesh."""
    mesh = mesh or flat_mesh()
    n = int(np.prod(mesh.devices.shape))
    sharding = NamedSharding(mesh, _stack_spec(mesh))

    def rep(t):
        # a host broadcast view + the sharding: device_put sends each
        # lane its own slice (building the stack with jnp first would
        # hold all n replicas on device 0 before resharding)
        t = np.asarray(t)
        return jax.device_put(np.broadcast_to(t[None], (n,) + t.shape),
                              sharding)
    return jax.tree_util.tree_map(rep, params)


def lane(tree, i: int = 0):
    """Extract one lane's replica (e.g. for eval / checkpointing)."""
    return jax.tree_util.tree_map(lambda t: np.asarray(t)[i], tree)


def lane_mean(tree):
    """Average the replicas (useful after model-averaging training)."""
    return jax.tree_util.tree_map(lambda t: np.asarray(t).mean(axis=0), tree)


def broadcast_variables(stacked, mesh: Optional[Mesh] = None, root: int = 0):
    """Overwrite every lane's replica with ``root``'s — the reference's
    BroadcastGlobalVariables initial/post-resize sync."""
    mesh = mesh or flat_mesh()
    axis = mesh.axis_names[0]

    def body(tree):
        # one masked psum per leaf — the collective lives in comm.collectives
        return jax.tree_util.tree_map(
            lambda t: C.broadcast(t[0], axis, root)[None], tree)

    fn = jax.jit(jax.shard_map(body, mesh=mesh,
                               in_specs=_stack_spec(mesh),
                               out_specs=_stack_spec(mesh)))
    return fn(stacked)


def _accum_grads_fn(loss_fn: Callable, axis: str, accum_steps: int,
                    has_aux: bool) -> Callable:
    """Microbatch gradient accumulation shared by the step builders.

    Returns ``grads_of(params, batch)`` (has_aux=False) or
    ``grads_of(params, mstate, batch)`` (has_aux=True, threading the model
    state sequentially through the scan).  Gradients and loss are averaged
    over ``accum_steps`` equal microbatches; the optimizer (and so the
    gradient allreduce) runs once on the result.
    """
    vg = jax.value_and_grad(loss_fn, has_aux=has_aux)

    def split(batch):
        for leaf in jax.tree_util.tree_leaves(batch):
            if leaf.shape[0] % accum_steps:
                raise ValueError(
                    f"per-lane batch {leaf.shape[0]} not divisible by "
                    f"accum_steps={accum_steps}")
        return jax.tree_util.tree_map(
            lambda t: t.reshape((accum_steps, t.shape[0] // accum_steps)
                                + t.shape[1:]), batch)

    def scan(params, micro, aux0):
        def acc_body(carry, mb):
            loss_acc, grad_acc, aux = carry
            if has_aux:
                (loss, aux), grads = vg(params, aux, mb)
            else:
                loss, grads = vg(params, mb)
            with jax.named_scope("accumulate"):
                return (loss_acc + loss,
                        jax.tree_util.tree_map(
                            lambda a, g: a + g.astype(a.dtype), grad_acc,
                            grads),
                        aux), None

        # carries must carry the mesh-varying axis the per-microbatch
        # loss/grads have inside shard_map (see shard_map#scan-vma):
        # zeros_like(params) inherits it from the sharded params; the
        # literal scalar loss carry needs an explicit cast.  The gradient
        # accumulator is ALWAYS f32 — with bf16 compute params, summing
        # microbatch grads in bf16 would truncate contributions once the
        # running sum outgrows them (8-bit mantissa)
        zeros = jax.tree_util.tree_map(
            lambda t: jnp.zeros_like(
                t, dtype=jnp.float32
                if jnp.issubdtype(t.dtype, jnp.floating) else None),
            params)
        loss0 = jax.lax.pcast(jnp.zeros(()), axis, to="varying")
        (loss_sum, grad_sum, aux), _ = jax.lax.scan(
            acc_body, (loss0, zeros, aux0), micro)
        k = float(accum_steps)
        # cast the f32-accumulated mean back to each param's dtype so the
        # accum path hands the optimizer the same grad dtypes as the
        # accum_steps=1 path (one rounding at the end, not k along the way)
        mean_grads = jax.tree_util.tree_map(
            lambda g, p: (g / k).astype(p.dtype), grad_sum, params)
        return loss_sum / k, mean_grads, aux

    if has_aux:
        def grads_of(params, mstate, batch):
            if accum_steps == 1:
                return vg(params, mstate, batch)
            loss, grads, ms = scan(params, split(batch), mstate)
            return (loss, ms), grads
    else:
        def grads_of(params, batch):
            if accum_steps == 1:
                return vg(params, batch)
            loss, grads, _ = scan(params, split(batch), ())
            return loss, grads
    return grads_of


def _cast_params(params, dtype):
    """f32 leaves -> ``dtype`` (non-float leaves untouched)."""
    return jax.tree_util.tree_map(
        lambda t: t.astype(dtype)
        if jnp.issubdtype(t.dtype, jnp.floating) else t, params)


def _mixed_precision(grads_of: Callable, compute_dtype, has_aux: bool):
    """Wrap a grads_of so the loss/grads run on a cast copy of the params
    while the caller keeps updating the f32 master (shared by both step
    builders — the casting rules must never diverge between them)."""
    if compute_dtype is None:
        return grads_of
    upcast = lambda grads, params: jax.tree_util.tree_map(
        lambda g, p: g.astype(p.dtype), grads, params)
    if has_aux:
        def wrapped(params, mstate, batch):
            out, grads = grads_of(_cast_params(params, compute_dtype),
                                  mstate, batch)
            return out, upcast(grads, params)
    else:
        def wrapped(params, batch):
            loss, grads = grads_of(_cast_params(params, compute_dtype),
                                   batch)
            return loss, upcast(grads, params)
    return wrapped


def _compiler_options(mesh: Mesh) -> dict:
    """XLA options for the accumulating step's program, by the mesh's
    platform: on TPU the memory scheduler is pinned to its list order.

    Left to itself XLA orders the program three ways (list, DFS,
    post-order) and keeps the order whose *estimated* peak is lowest.  For
    a step that sums gradients over microbatches the estimates tie (each
    order ends holding every gradient sum and the last gradient), a few
    MB decide, and what buffer assignment then needs differs by over a
    gigabyte: for one and the same Mistral step 8.36 GB of temporaries in
    DFS order against 6.93 GB in list order, which is the order built to
    keep memory low (PERF.md section 6, PR 28).  Pinning it keeps the
    step's memory from moving with an edit somewhere else in the model.
    Other backends do not know the option."""
    if mesh.devices.flat[0].platform != "tpu":
        return {}
    return {"xla_memory_scheduler": "list"}


def build_train_step(loss_fn: Callable,
                     optimizer: optax.GradientTransformation,
                     mesh: Optional[Mesh] = None,
                     donate: bool = True,
                     accum_steps: int = 1,
                     compute_dtype=None) -> Callable:
    """Compile a distributed train step.

    ``loss_fn(params, batch) -> scalar``.  The returned function has
    signature ``step(stacked_params, stacked_opt_state, global_batch) ->
    (stacked_params, stacked_opt_state, mean_loss)``; ``global_batch``'s
    leading axis is sharded across lanes.  All collective communication
    happens inside the optimizer's update and compiles into this one XLA
    program.

    ``accum_steps > 1`` enables gradient accumulation: each lane's batch
    shard is split into that many microbatches, gradients accumulate over
    a ``lax.scan`` (activation memory = one microbatch), and the optimizer
    — and therefore the gradient allreduce — runs ONCE on the mean.  The
    trajectory equals a single big-batch step.

    ``compute_dtype`` (e.g. ``jnp.bfloat16``): mixed-precision master
    weights — f32 params are cast ONCE per step, the loss/grads run in
    that dtype (the model's own per-use ``astype`` becomes a no-op), and
    the f32 master is updated with upcast gradients.  Without it, a model
    that casts weights inline re-pays the f32 read + cast on every
    microbatch of the accumulation scan.
    """
    mesh = mesh or flat_mesh()
    axis = mesh.axis_names[0]
    spec = _stack_spec(mesh)
    if accum_steps < 1:
        raise ValueError("accum_steps must be >= 1")

    grads_of = _mixed_precision(
        _accum_grads_fn(loss_fn, axis, accum_steps, has_aux=False),
        compute_dtype, has_aux=False)

    def body(stacked_params, stacked_state, batch):
        params = jax.tree_util.tree_map(lambda t: t[0], stacked_params)
        state = jax.tree_util.tree_map(lambda t: t[0], stacked_state)
        # the scopes are how a device trace is read (docs/monitoring.md,
        # "Scope names"): metadata of the program, nothing at run time.
        # The restacking stands inside `optimizer` because XLA names a
        # fusion after its root, and each update's is the `x[None]`
        restack = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        with jax.named_scope("grads"):
            loss, grads = grads_of(params, batch)
        with jax.named_scope("optimizer"):
            updates, state = optimizer.update(grads, state, params)
            params = optax.apply_updates(params, updates)
            params, state = restack(params), restack(state)
        with jax.named_scope("sync"):
            mean_loss = jax.lax.pmean(loss, axis)
        return params, state, mean_loss.reshape(1)

    sm = jax.shard_map(body, mesh=mesh,
                       in_specs=(spec, spec, spec),
                       out_specs=(spec, spec, spec))
    jit_kwargs = {"donate_argnums": (0, 1)} if donate else {}
    jitted = jax.jit(sm, compiler_options=_compiler_options(mesh),
                     **jit_kwargs)

    def step(stacked_params, stacked_state, global_batch):
        p, s, losses = jitted(stacked_params, stacked_state, global_batch)
        return p, s, losses
    # AOT access to the program behind the wrapper: inspect what it
    # lowers to, or compile once and call the executable
    step.lower = jitted.lower
    return step


def build_train_step_with_state(loss_fn: Callable,
                                optimizer: optax.GradientTransformation,
                                mesh: Optional[Mesh] = None,
                                sync_model_state: bool = True,
                                donate: bool = True,
                                accum_steps: int = 1,
                                compute_dtype=None) -> Callable:
    """Like build_train_step, for models with non-trained state (BatchNorm
    running stats).  ``loss_fn(params, model_state, batch) -> (loss,
    new_model_state)``.  When ``sync_model_state`` is set the new state is
    cross-replica averaged each step (the reference broadcasts BN stats with
    the rest of the variables on sync points).

    ``accum_steps > 1``: gradients accumulate over a microbatch scan as in
    :func:`build_train_step`; the model state threads through the scan
    sequentially (each microbatch sees the previous one's BN stats, the
    same as running the microbatches as separate steps)."""
    mesh = mesh or flat_mesh()
    axis = mesh.axis_names[0]
    spec = _stack_spec(mesh)
    if accum_steps < 1:
        raise ValueError("accum_steps must be >= 1")

    grads_of = _mixed_precision(
        _accum_grads_fn(loss_fn, axis, accum_steps, has_aux=True),
        compute_dtype, has_aux=True)

    def body(stacked_params, stacked_state, stacked_mstate, batch):
        params = jax.tree_util.tree_map(lambda t: t[0], stacked_params)
        state = jax.tree_util.tree_map(lambda t: t[0], stacked_state)
        mstate = jax.tree_util.tree_map(lambda t: t[0], stacked_mstate)
        # the same scopes as in build_train_step
        restack = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        with jax.named_scope("grads"):
            (loss, new_mstate), grads = grads_of(params, mstate, batch)
        with jax.named_scope("optimizer"):
            updates, state = optimizer.update(grads, state, params)
            params = optax.apply_updates(params, updates)
            params, state = restack(params), restack(state)
        with jax.named_scope("sync"):
            if sync_model_state:
                new_mstate = C.all_reduce(new_mstate, axis, "MEAN")
            mean_loss = jax.lax.pmean(loss, axis)
        return params, state, restack(new_mstate), mean_loss.reshape(1)

    sm = jax.shard_map(body, mesh=mesh,
                       in_specs=(spec, spec, spec, spec),
                       out_specs=(spec, spec, spec, spec))
    jit_kwargs = {"donate_argnums": (0, 1, 2)} if donate else {}
    return jax.jit(sm, **jit_kwargs)


def init_opt_state(optimizer: optax.GradientTransformation, stacked_params,
                   mesh: Optional[Mesh] = None):
    """Per-lane optimizer state, stacked and sharded like the params."""
    mesh = mesh or flat_mesh()
    spec = _stack_spec(mesh)

    def body(stacked):
        params = jax.tree_util.tree_map(lambda t: t[0], stacked)
        state = optimizer.init(params)
        return jax.tree_util.tree_map(lambda t: jnp.asarray(t)[None], state)

    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec))
    return fn(stacked_params)
