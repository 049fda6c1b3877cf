"""Pipeline parallelism (dp x pp) for the GPT model family.

GPipe-style microbatch pipelining, TPU-native: the layer stack is sharded
over the ``pp`` mesh axis (each stage holds a contiguous slice of layers),
activations flow stage -> stage+1 with ``lax.ppermute`` inside one
``lax.scan`` over S + M - 1 ticks (S stages, M microbatches), and the
*backward* pipeline — reverse ppermute schedule, bubble structure and all —
is generated by jax's AD transpose of that scan.  The whole pipeline is one
XLA program: no host-side scheduler, no per-stage processes (the
compiled-schedule property the reference's NCCL scheduler works to recreate
dynamically — SURVEY.md §5).

SPMD uniformity: every rank runs the same stage program each tick.
Embeddings for all M microbatches are computed ONCE before the tick loop
(rank 0 masks in the entering one per tick); the LM head + loss run ONCE
after the loop, on the M ticks whose outputs leave this rank (the last
stage's results are the ones that count — others are where-masked).
Bubble ticks compute the stage on garbage and drop out via the same
masks — cost (S+M-1)/M of ideal, amortized by more microbatches
(accounting measured against theory in benchmarks/pipeline.py).

``virtual_stages=v > 1`` switches to the Megatron-style INTERLEAVED
schedule: rank r holds the v layer chunks r, r+S, ..., r+(v-1)S, ticks
shrink to one chunk (1/v stage) of compute, and the bubble drops to
1 + (S-1)/(v*M) at v x the activation ppermutes (details + the
conflict-freedom argument in :func:`make_gpt_pp_train_step`).

Composes with data parallelism over the ``dp`` axis (gradient psums are
inserted by shard_map's varying-axis AD, as in threed.py).
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import gpt as G

DP_AXIS, PP_AXIS, TP_AXIS = "dp", "pp", "tp"


def mesh_dp_pp(dp: int, pp: int,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    from ..comm.mesh import make_mesh
    return make_mesh((DP_AXIS, PP_AXIS), (dp, pp), devices)


def mesh_dp_pp_tp(dp: int, pp: int, tp: int,
                  devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Megatron-style 3D: data x pipeline x tensor.  tp innermost so its
    per-matmul psums ride the closest ICI links."""
    from ..comm.mesh import make_mesh
    return make_mesh((DP_AXIS, PP_AXIS, TP_AXIS), (dp, pp, tp), devices)


def stack_layers(params):
    """[L x layer-dict] -> one dict with leading layer axis per leaf, so
    the layer stack shards over pp with a plain leading-axis split."""
    layers = params["layers"]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    out = dict(params)
    out["layers"] = stacked
    return out


def unstack_layers(params, n_layers: int):
    """Inverse of :func:`stack_layers` (e.g. for the single-device oracle)."""
    stacked = params["layers"]
    layers = [jax.tree_util.tree_map(lambda t: t[i], stacked)
              for i in range(n_layers)]
    out = dict(params)
    out["layers"] = layers
    return out


def pp_schedule_ticks(S: int, M: int, v: int = 1) -> int:
    """Exact tick count of the compiled schedule: the last unit is
    (chunk S*v-1, microbatch M-1), which runs at tick
    e(M-1) + S*v - 1 with entry schedule e(m) = (m//S)*v*S + m%S.
    Equals v*M + S - 1 when M is a multiple of S (the Megatron formula)
    and reduces to the GPipe S + M - 1 at v=1; for M < S it is
    (M-1) + S*v — the bubble does NOT amortize until microbatches at
    least fill the ranks."""
    return ((M - 1) // S) * v * S + (M - 1) % S + S * v


def interleave_layer_order(n_layers: int, S: int, v: int):
    """Layer permutation for interleaved virtual stages: global chunk
    ``c`` (of ``S*v``) lives on rank ``c % S`` as its ``c // S``-th local
    chunk.  Applying this order to the stacked layer axis makes each
    rank's contiguous pp shard hold exactly its ``v`` chunks, in local
    chunk order — so the shard reshapes to [v, layers/chunk, ...] with no
    device-side shuffling.  ``numpy`` array of layer indices."""
    import numpy as np
    if n_layers % (S * v):
        # validate HERE, not only in the train-step factory: a silent
        # floor division would return a truncated permutation and
        # init_gpt_pp would drop layers
        raise ValueError(f"{n_layers} layers not divisible by "
                         f"{S} pipeline stages x {v} virtual stages")
    Lc = n_layers // (S * v)
    order = []
    for r in range(S):
        for j in range(v):
            c = r + j * S
            order.extend(range(c * Lc, (c + 1) * Lc))
    return np.asarray(order, dtype=np.int64)


def pp_param_specs(cfg: G.GPTConfig, tp: Optional[str] = None) -> dict:
    """Layer stack sharded over pp (leading axis), optionally composed
    with Megatron tensor parallelism inside each stage (``tp``: heads/
    features sharded per-layer, vocab-sharded lm_head).  Embeddings stay
    replicated (used only on the first/last stage, masked — their grads
    on other ranks are zero and the AD-inserted psum over pp restores the
    total); FSDP over the dp axis is the supported way to shard them."""
    base = G.param_specs(cfg, tp=tp)
    layer = {k: P(PP_AXIS, *s) for k, s in base["layers"][0].items()}
    out = {"wte": P(), "layers": layer,
           "lnf": P(), "lm_head": base["lm_head"]}
    if not cfg.rope:
        out["wpe"] = P()
    return out


def _stage_forward(layers_local, x, cfg: G.GPTConfig,
                   tp_axis: Optional[str] = None, pos=None):
    """Apply this stage's layer slice (leading axis = local layers)."""
    n_local = jax.tree_util.tree_leaves(layers_local)[0].shape[0]
    for i in range(n_local):  # unrolled: n_local is static and small
        layer = jax.tree_util.tree_map(lambda t: t[i], layers_local)
        x = G.apply_layer(layer, x, cfg, tp_axis=tp_axis, sp_axis=None,
                          attn="dense", pos=pos)
    return x


def make_gpt_pp_train_step(cfg: G.GPTConfig,
                           optimizer: optax.GradientTransformation,
                           mesh: Mesh,
                           n_micro: int,
                           donate: bool = True,
                           remat: bool = False,
                           virtual_stages: int = 1) -> Callable:
    """Compile ``step(params, opt_state, tokens, targets) -> (params,
    opt_state, loss)`` with the layer stack pipelined over ``pp`` and the
    batch sharded over ``dp``.

    ``params`` must be in stacked-layer form (:func:`stack_layers`),
    sharded per :func:`pp_param_specs`.  The local batch must divide into
    ``n_micro`` microbatches.  On a 3-axis (dp, pp, tp) mesh the stages
    additionally run Megatron tensor parallelism (heads/features sharded,
    vocab-sharded head with parallel cross-entropy).

    ``remat=True`` checkpoints each tick's stage compute: the backward
    re-runs the stage layers instead of keeping their internals across
    all S+M-1 ticks — activation residency drops from O(ticks x layer
    internals) to O(ticks x activation) + one stage recompute, the GPipe
    memory shape at scale (cost: ~1.3x forward FLOPs in the backward).

    Schedule accounting (measured against theory in
    benchmarks/pipeline.py): GPipe fills and drains once per step, so
    compute-bubble overhead is (S+M-1)/M of ideal — amortized by more
    microbatches.  A 1F1B reordering would NOT change this compute
    bubble (it changes peak ACTIVATION memory, which ``remat`` addresses
    here).

    ``virtual_stages = v > 1`` runs the Megatron-style INTERLEAVED
    schedule: the layer stack splits into S*v chunks, rank r holds
    chunks r, r+S, ..., r+(v-1)S (params must be permuted by
    :func:`interleave_layer_order` — :func:`init_gpt_pp` does this), and
    the scan runs :func:`pp_schedule_ticks` (S, M, v) ticks of ONE chunk
    (1/v of a stage) each.  Microbatch m enters at tick
    e(m) = (m//S)*v*S + m%S; unit (chunk c, microbatch m) runs at tick
    e(m) + c on rank c % S — provably conflict-free (one chunk per rank
    per tick; entry ticks of distinct in-flight microbatches never
    differ by a multiple of S smaller than v*S).  For M a multiple of S
    the tick count is v*M + S - 1, i.e. compute-bubble overhead
    1 + (S-1)/(v*M) — the Megatron interleaved bubble — at v x the
    ppermute traffic (the transfer is one [mb, T, d] tile per tick
    either way); for M < S it is (M-1) + S*v ticks, still below
    GPipe's overhead but short of the asymptotic formula.
    """
    G._plain_layers_only(cfg, "make_gpt_pp_train_step")
    tp = TP_AXIS if TP_AXIS in mesh.axis_names else None
    specs = pp_param_specs(cfg, tp=tp)
    data_spec = P(DP_AXIS, None)
    S = mesh.devices.shape[mesh.axis_names.index(PP_AXIS)]
    v = virtual_stages
    if v < 1:
        raise ValueError(f"virtual_stages must be >= 1, got {v}")
    if cfg.n_layers % (S * v) != 0:
        raise ValueError(f"{cfg.n_layers} layers not divisible by "
                         f"{S} pipeline stages x {v} virtual stages")
    if tp is not None:
        ntp = mesh.devices.shape[mesh.axis_names.index(TP_AXIS)]
        for what, val in (("n_heads", cfg.n_heads), ("d_ff", cfg.d_ff),
                          ("vocab_size", cfg.vocab_size)):
            if val % ntp != 0:
                raise ValueError(f"{what}={val} not divisible by "
                                 f"{ntp} tensor-parallel ranks")
    M = n_micro

    def grad_body(params, tokens, targets):
        dp_size = lax.axis_size(DP_AXIS)
        stage = lax.axis_index(PP_AXIS)
        B, T = tokens.shape
        if B % M != 0:
            raise ValueError(f"local batch {B} not divisible by "
                             f"{M} microbatches")
        mb = B // M
        total = B * T * dp_size  # global token count (pp replicates data)
        tok_mb = tokens.reshape(M, mb, T)
        tgt_mb = targets.reshape(M, mb, T)
        pos = jnp.arange(T)
        perm = [(i, i + 1) for i in range(S - 1)]  # forward shift

        def local_loss_interleaved(p):
            """v > 1: one chunk (cfg.n_layers // (S*v) layers) per tick;
            see the virtual_stages paragraph in the factory docstring for
            the schedule and its conflict-freedom."""
            Lc = cfg.n_layers // (S * v)
            embeds = jax.vmap(
                lambda tk: G.embed(p, tk, pos[None], cfg))(tok_mb)
            # pp shard of the (interleave-permuted) stack -> local chunks
            chunks = jax.tree_util.tree_map(
                lambda t: t.reshape((v, Lc) + t.shape[1:]), p["layers"])

            chunk_fn = lambda cp, xi: _stage_forward(cp, xi, cfg,
                                                     tp_axis=tp, pos=pos)
            if remat:
                chunk_fn = jax.checkpoint(chunk_fn)

            # chunk transitions always hop rank r -> r+1 mod S (chunk
            # c on rank c%S, chunk c+1 on rank (c+1)%S): a ring shift,
            # unlike the v=1 open chain
            ring = [(i, (i + 1) % S) for i in range(S)]
            n_ticks = pp_schedule_ticks(S, M, v)

            def tick(carry, t):
                x_buf, out_buf = carry
                # decode (j, m) for this rank at tick t: t - stage =
                # q*(v*S) + j*S + s  with  m = q*S + s
                a = jnp.maximum(t - stage, 0)
                s_idx = a % S
                b = a // S
                j = b % v
                q = b // v
                m = q * S + s_idx
                valid = (t >= stage) & (m < M)
                m_safe = jnp.clip(m, 0, M - 1)
                inject = (stage == 0) & (j == 0)
                x_in = jnp.where(
                    inject,
                    lax.dynamic_index_in_dim(embeds, m_safe, 0,
                                             keepdims=False), x_buf)
                cp = jax.tree_util.tree_map(
                    lambda tt: lax.dynamic_index_in_dim(tt, j, 0,
                                                        keepdims=False),
                    chunks)
                y = chunk_fn(cp, x_in)
                # the last chunk's outputs land in microbatch order; the
                # masked select keeps the buffer untouched elsewhere
                is_final = (stage == S - 1) & (j == v - 1) & valid
                out_buf = jnp.where(
                    is_final,
                    lax.dynamic_update_index_in_dim(
                        out_buf, y.astype(out_buf.dtype), m_safe, 0),
                    out_buf)
                return (lax.ppermute(y, PP_AXIS, perm=ring), out_buf), None

            x0 = lax.pcast(jnp.zeros((mb, T, cfg.d_model), cfg.dtype),
                           (DP_AXIS, PP_AXIS), to="varying")
            o0 = lax.pcast(jnp.zeros((M, mb, T, cfg.d_model), cfg.dtype),
                           (DP_AXIS, PP_AXIS), to="varying")
            (_, outs), _ = lax.scan(tick, (x0, o0), jnp.arange(n_ticks))

            def head_nll(y_tgt):
                y, tgt = y_tgt
                xf = G.rms_norm(y, p["lnf"], cfg.norm_eps)
                logits = jnp.einsum("btd,dv->btv", xf.astype(jnp.float32),
                                    p["lm_head"])
                return G.parallel_cross_entropy(logits, tgt,
                                                tp_axis=tp).sum()

            nll = lax.map(head_nll, (outs, tgt_mb))
            loss_sum = jnp.where(stage == S - 1, nll.sum(), 0.0)
            return loss_sum / total

        def local_loss(p):
            # embed ONCE for all M microbatches before the loop; the tick
            # only indexes (the old loop re-embedded every tick on every
            # rank, masked — pure waste, S+M-2 extra embeds per rank)
            embeds = jax.vmap(
                lambda tk: G.embed(p, tk, pos[None], cfg))(tok_mb)

            stage_fn = lambda lp, xi: _stage_forward(lp, xi, cfg,
                                                     tp_axis=tp, pos=pos)
            if remat:
                stage_fn = jax.checkpoint(stage_fn)

            def tick(x_buf, t):
                mb_idx = t - stage                  # this rank's microbatch
                safe = jnp.clip(mb_idx, 0, M - 1)
                # stage 0 injects the entering microbatch; others take the
                # ppermuted activation from the previous stage.  Masked
                # select rather than lax.cond: a cond on the per-rank
                # stage index inside this scan aborts XLA's runtime
                # (observed on the CPU backend).
                x_in = jnp.where(
                    stage == 0,
                    lax.dynamic_index_in_dim(embeds, safe, 0,
                                             keepdims=False), x_buf)
                y = stage_fn(p["layers"], x_in)
                return lax.ppermute(y, PP_AXIS, perm=perm), y

            # the carry is rank-varying from tick 1 on; pcast aligns the
            # initial (replicated-constant) carry's type with that
            x0 = lax.pcast(jnp.zeros((mb, T, cfg.d_model), cfg.dtype),
                           (DP_AXIS, PP_AXIS), to="varying")
            _, ys = lax.scan(tick, x0, jnp.arange(S + M - 1))

            # LM head AFTER the loop, on exactly the M ticks whose
            # outputs leave this rank (ticks stage .. stage+M-1) — the
            # old loop ran the head matmul every tick on every rank
            # (S+M-1 applications, all but M masked garbage).  Stage
            # uniformity holds: every rank slices M ticks and computes
            # the same-shaped head; only the last stage's result counts.
            yv = lax.dynamic_slice_in_dim(ys, stage, M, axis=0)

            def head_nll(y_tgt):
                y, tgt = y_tgt
                xf = G.rms_norm(y, p["lnf"], cfg.norm_eps)
                logits = jnp.einsum("btd,dv->btv", xf.astype(jnp.float32),
                                    p["lm_head"])  # vocab-sharded under tp
                return G.parallel_cross_entropy(logits, tgt,
                                                tp_axis=tp).sum()

            # sequential over microbatches: peak logits memory stays one
            # [mb, T, vocab] tile, as in the per-tick version
            nll = lax.map(head_nll, (yv, tgt_mb))
            loss_sum = jnp.where(stage == S - 1, nll.sum(), 0.0)
            return loss_sum / total

        loss_fn = local_loss if v == 1 else local_loss_interleaved
        lval, grads = jax.value_and_grad(loss_fn)(params)
        # grad psums per replication state are inserted by shard_map AD
        loss = lax.psum(lval, (DP_AXIS, PP_AXIS))
        return loss, grads

    sm = jax.shard_map(grad_body, mesh=mesh,
                       in_specs=(specs, data_spec, data_spec),
                       out_specs=(P(), specs))

    def step(params, opt_state, tokens, targets):
        loss, grads = sm(params, tokens, targets)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    kwargs = {"donate_argnums": (0, 1)} if donate else {}
    return jax.jit(step, **kwargs)


def init_gpt_pp(cfg: G.GPTConfig, optimizer: optax.GradientTransformation,
                mesh: Mesh, seed: int = 0, virtual_stages: int = 1):
    """Stacked-layer params sharded over the (dp, pp[, tp]) mesh + opt
    state.  ``virtual_stages > 1`` permutes the stacked layer axis by
    :func:`interleave_layer_order` so each rank's contiguous pp shard
    holds its v interleaved chunks (undo with
    :func:`deinterleave_params` before comparing to an unpermuted
    model)."""
    params = stack_layers(G.init_params(jax.random.PRNGKey(seed), cfg))
    if virtual_stages > 1:
        S = mesh.devices.shape[mesh.axis_names.index(PP_AXIS)]
        order = interleave_layer_order(cfg.n_layers, S, virtual_stages)
        params["layers"] = jax.tree_util.tree_map(
            lambda t: t[order], params["layers"])
    specs = pp_param_specs(cfg, tp=TP_AXIS if TP_AXIS in mesh.axis_names
                           else None)
    params = jax.tree_util.tree_map(
        lambda t, s: jax.device_put(t, NamedSharding(mesh, s)),
        params, specs)
    opt_state = jax.jit(optimizer.init)(params)
    return params, opt_state


def deinterleave_params(params, n_layers: int, S: int, v: int):
    """Undo :func:`init_gpt_pp`'s interleave permutation (host-side), so
    interleaved-trained params compare leaf-for-leaf with the natural
    layer order."""
    import numpy as np
    order = interleave_layer_order(n_layers, S, v)
    inv = np.empty_like(order)
    inv[order] = np.arange(len(order))
    out = dict(params)
    out["layers"] = jax.tree_util.tree_map(lambda t: t[inv],
                                           params["layers"])
    return out
