"""Expert parallelism: switch-style Mixture-of-Experts over an ``ep`` axis.

The reference framework has no expert parallelism (SURVEY.md §2.4); this is
the TPU-native extension completing the parallelism matrix (dp/tp/sp/pp/ep).

Design (the canonical TPU MoE dataflow):

- top-1 (switch) routing with a static per-expert **capacity** — dispatch
  and combine are dense one-hot einsums, so shapes stay static and the MXU
  does the work; overflow tokens pass through the residual unchanged,
- experts sharded over ``ep`` (each rank owns ``E / ep`` expert MLPs),
- tokens travel to their expert's owner and back with two tiled
  ``lax.all_to_all``s — the ``ep`` analogue of Ulysses' head re-sharding,
- a switch load-balancing auxiliary loss (E * Σ_e fraction_e * prob_e),
  pmean'd across the mesh.

Composes with data parallelism: batch axes (dp and ep both carry tokens
outside the expert block) shard the tokens; only the expert weights are
ep-sharded.  Gradient psums are inserted by shard_map's varying-axis AD.

Beside it, the layer large sparse models train with: routing over all
experts (:func:`route_topk`: a softmax, or sigmoid scores chosen with a
bias that the train step moves by the experts' load,
:func:`update_expert_bias`), the top k of them a token with the weights
normalised over the k, no capacity and no dropped token, gated experts
(:func:`expert_ffn`: ReGLU or SwiGLU; :func:`dropless_moe_ffn` is the
softmax router with ReGLU experts), and a share of the experts held
here: the layer is told which contiguous range of expert ids its weights
are, routes over all of them, and computes the part of the result its own
experts give.  The held assignments are sorted by expert into a row
buffer of which only the integer arrays are made; a pass is one loop over
its blocks in use (:func:`expert_ffn`), so the work follows the rows held,
not the buffer's static size.  :func:`grouped_matmul` is the same product
alone, behind ``jax.lax.ragged_dot``'s signature.  On one chip there is no
exchange; what the experts held elsewhere would add is left out.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.row_scatter import carry_shape, rows_of, scatter_add_rows


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int = 64
    d_ff: int = 256          # per-expert hidden width
    n_experts: int = 8
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16  # expert-compute dtype (routing stays f32)


def mesh_dp_ep(dp: int, ep: int,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    from ..comm.mesh import make_mesh
    return make_mesh(("dp", "ep"), (dp, ep), devices)


def init_moe_params(rng: jax.Array, cfg: MoEConfig) -> dict:
    """Router (replicated) + stacked expert MLPs (leading axis = expert,
    sharded over ep)."""
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "router": jax.random.normal(k1, (D, E), jnp.float32) / np.sqrt(D),
        "wi": jax.random.normal(k2, (E, D, F), jnp.float32) / np.sqrt(D),
        "wo": jax.random.normal(k3, (E, F, D), jnp.float32) / np.sqrt(F),
    }


def moe_param_specs(ep: Optional[str] = "ep") -> dict:
    return {"router": P(), "wi": P(ep, None, None), "wo": P(ep, None, None)}


def _capacity(n_tokens: int, cfg: MoEConfig) -> int:
    c = int(math.ceil(n_tokens * cfg.capacity_factor / cfg.n_experts))
    return max(c, 1)


def moe_ffn(params: dict, x, cfg: MoEConfig,
            ep_axis: Optional[str] = None,
            residual: bool = True) -> Tuple[Any, Any]:
    """Apply the MoE FFN to (local) activations ``x`` [B, T, D].

    With ``ep_axis``, ``params["wi"]/["wo"]`` hold the local expert slice
    ``[E/ep, ...]`` and tokens are exchanged with two all_to_alls; without
    it they hold all ``E`` experts (the oracle).  Returns ``(y, aux_loss)``
    where ``y`` includes the residual (overflowed tokens pass through);
    ``residual=False`` returns just the expert contribution, for callers
    (pre-norm transformers) that add their own residual on the un-normed
    stream.
    """
    B, T, D = x.shape
    E = cfg.n_experts
    n = B * T
    C = _capacity(n, cfg)
    xt = x.reshape(n, D)

    # ---- routing (f32): top-1 expert + gate -----------------------------
    logits = xt.astype(jnp.float32) @ params["router"]          # [n, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate = jnp.max(probs, axis=-1)                              # [n]
    expert = jnp.argmax(probs, axis=-1)                         # [n]

    # switch load-balancing loss: E * sum_e fraction_e * mean-prob_e
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)       # [n, E]
    fraction = onehot.mean(axis=0)
    mean_prob = probs.mean(axis=0)
    aux = E * jnp.sum(fraction * mean_prob)
    if ep_axis:
        aux = lax.pmean(aux, ep_axis)

    # ---- dense dispatch within capacity ---------------------------------
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0             # [n, E]
    pos = pos.astype(jnp.int32)
    keep = (pos >= 0) & (pos < C)
    disp = (jax.nn.one_hot(jnp.where(keep, pos, -1), C, dtype=xt.dtype)
            * onehot[..., None].astype(xt.dtype))               # [n, E, C]
    comb = disp.astype(jnp.float32) * gate[:, None, None]       # [n, E, C]

    # expert compute runs in cfg.dtype (bf16 on TPU); routing/combine f32
    buf = jnp.einsum("nec,nd->ecd", disp.astype(cfg.dtype),
                     xt.astype(cfg.dtype))                      # [E, C, D]

    # ---- expert compute (locally, or via all_to_all over ep) ------------
    if ep_axis:
        ep = lax.axis_size(ep_axis)
        e_local = params["wi"].shape[0]
        # send each expert-block to its owner (tiled over leading axis)
        buf = lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=0,
                             tiled=True)                        # [E, C, D]
        # [src, e_local, C, D] -> per-expert batches [e_local, src*C, D]
        buf = (buf.reshape(ep, e_local, C, D).transpose(1, 0, 2, 3)
               .reshape(e_local, ep * C, D))
    else:
        e_local = E

    def one_expert(b, wi, wo):
        h = jax.nn.gelu(b @ wi.astype(b.dtype))
        return h @ wo.astype(b.dtype)

    out = jax.vmap(one_expert)(buf, params["wi"], params["wo"])

    if ep_axis:
        out = (out.reshape(e_local, ep, C, D).transpose(1, 0, 2, 3)
               .reshape(E, C, D))
        out = lax.all_to_all(out, ep_axis, split_axis=0, concat_axis=0,
                             tiled=True)                        # [E, C, D]

    y = jnp.einsum("nec,ecd->nd", comb, out.astype(jnp.float32))
    y = y.astype(x.dtype).reshape(B, T, D)
    if residual:
        y = x + y          # overflow -> pure residual
    return y, aux


def make_moe_step(cfg: MoEConfig, optimizer, mesh: Mesh,
                  aux_weight: float = 0.01, donate: bool = True):
    """Compile a toy regression train step over a (dp, ep) mesh — the
    correctness harness for the MoE dataflow (batch sharded over dp x ep,
    experts over ep).  ``step(params, opt_state, x, y) -> (params,
    opt_state, loss)``."""
    import optax

    dp_axis, ep_axis = mesh.axis_names
    ep = mesh.devices.shape[1]
    if cfg.n_experts % ep != 0:
        raise ValueError(f"{cfg.n_experts} experts not divisible by "
                         f"{ep} expert-parallel ranks")
    data_spec = P((dp_axis, ep_axis))
    specs = moe_param_specs(ep_axis)

    def grad_body(params, x, y):
        def local_loss(p):
            out, aux = moe_ffn(p, x, cfg, ep_axis=ep_axis)
            mse = jnp.mean((out.astype(jnp.float32)
                            - y.astype(jnp.float32)) ** 2)
            mse = lax.pmean(mse, (dp_axis, ep_axis))
            aux = lax.pmean(aux, dp_axis)
            return mse + aux_weight * aux
        lval, grads = jax.value_and_grad(local_loss)(params)
        return lval, grads

    sm = jax.shard_map(grad_body, mesh=mesh,
                       in_specs=(specs, data_spec, data_spec),
                       out_specs=(P(), specs))

    def step(params, opt_state, x, y):
        loss, grads = sm(params, x, y)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    kwargs = {"donate_argnums": (0, 1)} if donate else {}
    return jax.jit(step, **kwargs)


# ------------------------------------------------- an expert layer's share
def route_topk(h, router, k: int, score: str = "softmax", bias=None):
    """Routing in float32 over all of ``router``'s experts: ``h`` [n, D],
    ``router`` [D, E] -> ``(ids [n, k] int32, weights [n, k] f32)``, each
    token's k experts, first the best (of equals the lower id).

    ``score="softmax"``: the k most probable of a softmax over all E, their
    probabilities divided by their sum over the k.  ``score="sigmoid"``
    (LFM2, DeepSeek-V3): each expert's own ``sigmoid`` score; the k are
    chosen by score + ``bias`` ([E], default nought), which moves the
    choice and never a weight, and the k scores are divided by their sum
    plus 1e-6."""
    with jax.named_scope("moe_route"):
        logits = jnp.einsum("nd,de->ne", h.astype(jnp.float32),
                            router.astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        if score == "sigmoid":
            probs = jax.nn.sigmoid(logits)
            _, ids = lax.top_k(probs if bias is None
                               else probs + bias.astype(jnp.float32), k)
            weights = jnp.take_along_axis(probs, ids, axis=-1)
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                                 + 1e-6)
            return ids.astype(jnp.int32), weights
        if score != "softmax" or bias is not None:
            raise ValueError(f"score {score!r} with a bias: only the sigmoid "
                             f"router takes one")
        probs = jax.nn.softmax(logits, axis=-1)
        weights, ids = lax.top_k(probs, k)
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return ids.astype(jnp.int32), weights


def update_expert_bias(bias, load, rate: float):
    """DeepSeek-V3's auxiliary-loss-free balancing (arXiv:2412.19437
    section 2.1.2): after a step each expert's bias on the choice moves by
    ``rate`` towards the mean load, ``bias + rate * sign(mean - load)``; no
    gradient is taken of it."""
    load = load.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(load) - load)


def _visits(group_sizes, n_rows: int, block: int):
    """Which (row block, group) pairs a grouped product has to visit:
    ``(n_visits, group [V], block [V], starts [G], ends [G])``.  Group g
    holds rows ``starts[g] <= r < ends[g]``, laid end to end; a visit is a
    row block that holds rows of the group, so a group of n rows takes at
    most ``n // block + 2`` of them and all groups together at most
    ``V = n_rows // block + G - 1``.  ``n_visits`` is a traced number: what
    the loops below run to."""
    G = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // block
    per_group = jnp.where(sizes > 0, (ends - 1) // block - first + 1, 0)
    visit_ends = jnp.cumsum(per_group)
    v = jnp.arange(n_rows // block + G - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.searchsorted(visit_ends, v, side="right"),
                        G - 1).astype(jnp.int32)
    blk = first[group] + v - (visit_ends - per_group)[group]
    return visit_ends[-1], group, jnp.clip(blk, 0, n_rows // block - 1), \
        starts, ends


def _zeros_like_of(like, shape, dtype):
    """Zeros that vary over the mesh axes ``like`` varies over: inside
    ``shard_map`` a loop's carry has to be of one type going in and coming
    out (shard_map#scan-vma).  What is filled is what a pass sums into:
    the tokens' result, and in the backward the tokens' and the weights'
    cotangents.  (An unwritten buffer, ``lax.empty``, gets memory of its
    own for the whole program from the chip's compiler: PERF.md section
    6, PR 35.)"""
    z = jnp.zeros(shape, dtype)
    vma = tuple(getattr(jax.typeof(like), "vma", ()) or ())
    return lax.pcast(z, vma, to="varying") if vma else z


def _dot(a, b, i: int, j: int):
    """``a`` and ``b`` contracted over their axes ``i`` and ``j``, the
    operands in their own dtype, the sum in float32."""
    return lax.dot_general(a, b, (((i,), (j,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _add_to_group(dw, g, part):
    """``dw[g] += part``."""
    old = lax.dynamic_index_in_dim(dw, g, keepdims=False)
    return lax.dynamic_update_index_in_dim(dw, old + part, g, 0)


def _gmm(rows, weights, group_sizes, block: int, transposed: bool,
         whole: bool = False):
    """``rows [M, K] x weights [G, K, N]`` by groups of rows (``transposed``:
    ``weights [G, N, K]``, contracted over their last axis).  ``whole``:
    every group is whole blocks, so a visit writes its block whole, with no
    mask and without reading what was there."""
    M = rows.shape[0]
    N = weights.shape[1] if transposed else weights.shape[2]
    n_visits, group, blk, starts, ends = _visits(group_sizes, M, block)

    def visit(v, out):
        g, at = group[v], blk[v] * block
        x = lax.dynamic_slice_in_dim(rows, at, block)
        w = lax.dynamic_index_in_dim(weights, g, keepdims=False)
        y = _dot(x, w, 1, 1 if transposed else 0).astype(out.dtype)
        if not whole:
            r = at + jnp.arange(block, dtype=jnp.int32)
            mine = ((r >= starts[g]) & (r < ends[g]))[:, None]
            y = jnp.where(mine, y, lax.dynamic_slice_in_dim(out, at, block))
        return lax.dynamic_update_slice_in_dim(out, y, at, 0)

    return lax.fori_loop(0, n_visits, visit,
                         _zeros_like_of(rows, (M, N), rows.dtype))


def _gmm_dw(rows, dout, group_sizes, n_groups: int, block: int,
            whole: bool = False):
    """Each group's ``rows_g^T dout_g``: [G, K, N] in float32."""
    M, K = rows.shape
    N = dout.shape[1]
    n_visits, group, blk, starts, ends = _visits(group_sizes, M, block)

    def visit(v, dw):
        g, at = group[v], blk[v] * block
        x = lax.dynamic_slice_in_dim(rows, at, block)
        if not whole:
            r = at + jnp.arange(block, dtype=jnp.int32)
            x = jnp.where(((r >= starts[g]) & (r < ends[g]))[:, None], x, 0)
        d = lax.dynamic_slice_in_dim(dout, at, block)
        return _add_to_group(dw, g, _dot(x, d, 0, 0))

    return lax.fori_loop(
        0, n_visits, visit,
        _zeros_like_of(rows, (n_groups, K, N), jnp.float32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped_matmul(rows, weights, group_sizes, block, whole):
    with jax.named_scope("gmm"):
        return _gmm(rows, weights, group_sizes, block, False, whole)


def _grouped_matmul_fwd(rows, weights, group_sizes, block, whole):
    return (_grouped_matmul(rows, weights, group_sizes, block, whole),
            (rows, weights, group_sizes))


def _grouped_matmul_bwd(block, whole, res, dout):
    rows, weights, group_sizes = res
    dout = dout.astype(rows.dtype)
    with jax.named_scope("gmm"):
        drows = _gmm(dout, weights, group_sizes, block, True, whole)
        dw = _gmm_dw(rows, dout, group_sizes, weights.shape[0], block, whole)
    return drows, dw.astype(weights.dtype), None


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def grouped_matmul(rows, weights, group_sizes, block_rows: int = 512,
                   whole_blocks: bool = False):
    """``jax.lax.ragged_dot``'s product by its signature: ``rows`` [M, K]
    lie sorted by group, the first ``group_sizes[0]`` rows are group 0's
    and so on; row r of group g gives ``rows[r] @ weights[g]`` (``weights``
    [G, K, N]) and a row past the groups gives nought.  -> [M, N].

    Plain JAX whose work follows the rows held: a loop over the row blocks
    in use (:func:`_visits`; a block that holds rows of two groups is
    visited for each), one group's weights a visit, so it multiplies the
    held rows and less than ``block_rows`` a group more, never all M.  jax
    does not reverse a loop of traced length, so the backward is written
    out (``custom_vjp``): the rows' cotangent is the same loop over the
    weights transposed, a group's weight cotangent the sum over its visits
    of ``rows^T dout``.  Operands are multiplied in their own dtype with
    float32 accumulation.  ``M`` must be a multiple of ``block_rows``
    (shorter buffers take one block).  The scope ``gmm`` stands around
    both passes.

    ``whole_blocks``: the caller's promise that every group is a whole
    number of blocks.  A visit then writes its block without a mask and
    without reading what was there, in the backward too."""
    M = rows.shape[0]
    block = min(block_rows, M)
    if M % block:
        raise ValueError(f"{M} rows are no multiple of block_rows={block}")
    return _grouped_matmul(rows, weights, group_sizes, block, whole_blocks)


def held_assignments(ids, held: Tuple[int, int]):
    """How many of the assignments ``ids`` [n, k] go to each expert held
    here (``held`` = first id, count): [count] int32."""
    local = ids - held[0]
    return jnp.sum((local[..., None] == jnp.arange(held[1])).reshape(
        -1, held[1]), axis=0, dtype=jnp.int32)


def row_buffer(ids, n: int, held: Tuple[int, int], block: int):
    """Where the held assignments of ``ids`` [n, k] lie in the row buffer:
    sorted by expert, each expert's group padded to whole blocks, so that
    a block holds rows of one expert, in the order of their tokens.
    ``M = (ceil(n k / block) + G) block`` rows have room for every
    assignment and the padding.  Returns

    - ``sizes`` [G]: each held expert's rows with its padding (multiples of
      ``block``), and ``n_rows``, their sum: the rows in use;
    - ``tok`` [M]: a live row's token; 0 for padding;
    - ``which`` [M], ``live`` [M]: the assignment (index into the flattened
      ``ids``) a live row stands for;
    - ``dest`` [n k], ``here`` [n k]: the row of an assignment that is held
      here."""
    G, k = held[1], ids.shape[1]
    A = n * k
    M = (-(-A // block) + G) * block
    local = (ids - held[0]).reshape(-1)                         # [A]
    here = (local >= 0) & (local < G)
    key = jnp.where(here, local, G)             # held elsewhere: sorted last
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    rank = jnp.argsort(order).astype(jnp.int32)
    count = held_assignments(ids, held)
    first = jnp.cumsum(count) - count           # in the sorted order
    sizes = -(-count // block) * block
    ends = jnp.cumsum(sizes)
    starts = ends - sizes                       # in the buffer
    b = jnp.arange(M, dtype=jnp.int32)
    g = jnp.minimum(jnp.searchsorted(ends, b, side="right"),
                    G - 1).astype(jnp.int32)
    j = b - starts[g]
    live = (b < ends[-1]) & (j < count[g])
    which = order[jnp.clip(first[g] + j, 0, A - 1)]
    tok = jnp.where(live, which // k, 0)
    mine = jnp.minimum(key, G - 1)
    dest = starts[mine] + rank - first[mine]
    return sizes, ends[-1], tok, which, live, dest, here


_GATES = {"reglu": jax.nn.relu, "swiglu": jax.nn.silu}


def _block_forward(x, scale, wi, wm, group, tok, i, block: int, act: str):
    """Block ``i`` of the row buffer through its expert: ``(g, idx, s, rows,
    w1, w2, gate, up, a, o)``, the expert, the block's tokens and scales,
    their rows of ``x``, the expert's two weights, gate and up, the gated
    activation ``act`` (ReGLU or SwiGLU) and the output [block, D] in
    float32."""
    g = group[i]
    with jax.named_scope("moe_route"):
        idx = lax.dynamic_slice_in_dim(tok, i * block, block)
        s = lax.dynamic_slice_in_dim(scale, i * block, block)
        rows = x[idx]
    with jax.named_scope("gmm"):
        w1 = lax.dynamic_index_in_dim(wi, g, keepdims=False).astype(x.dtype)
        w2 = lax.dynamic_index_in_dim(wm, g, keepdims=False).astype(x.dtype)
        h = _dot(rows, w1, 1, 0).astype(x.dtype)
    with jax.named_scope("moe_act"):
        gate, up = h[:, :w2.shape[0]], h[:, w2.shape[0]:]
        a = _GATES[act](gate) * up
    with jax.named_scope("gmm"):
        o = _dot(a, w2, 1, 0)
    return g, idx, s, rows, w1, w2, gate, up, a, o


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _expert_rows(x, weights, wi, wm, buffer, block, act):
    """:func:`expert_ffn` given where the rows lie: ``buffer`` is the
    blocks in use, each block's expert, :func:`row_buffer`'s ``tok``, each
    block's live rows (a prefix of it), and its ``which``, ``live``,
    ``dest``, ``here``."""
    return _expert_rows_fwd(x, weights, wi, wm, buffer, block, act)[0]


def _expert_rows_fwd(x, weights, wi, wm, buffer, block, act):
    n_blocks, group, tok, n_live, which, live, dest, here = buffer
    with jax.named_scope("moe_route"):
        scale = jnp.where(live, weights.reshape(-1)[which], 0)

    def visit(i, y):
        _, idx, s, *_, o = _block_forward(x, scale, wi, wm, group, tok, i,
                                          block, act)
        with jax.named_scope("moe_route"):
            return scatter_add_rows(y, idx, n_live[i], o * s[:, None])

    y = lax.fori_loop(0, n_blocks, visit, _zeros_like_of(
        x, carry_shape(*x.shape), jnp.float32))
    # kept: the inputs, a scale a row and a live count a block, nothing
    # [M, .] of the buffer
    return rows_of(y, x.shape[1], x.dtype), (
        x, scale, wi, wm, n_blocks, group, tok, n_live, dest, here)


def _expert_rows_bwd(block, act, res, dy):
    x, scale, wi, wm, n_blocks, group, tok, n_live, dest, here = res

    def visit(i, carry):
        dx, dscale, dwi, dwm = carry
        g, idx, s, rows, w1, w2, gate, up, a, o = _block_forward(
            x, scale, wi, wm, group, tok, i, block, act)
        with jax.named_scope("moe_route"):
            d = dy[idx].astype(jnp.float32)
            dscale = lax.dynamic_update_slice_in_dim(
                dscale, jnp.sum(d * o, -1), i * block, 0)
            do = (d * s[:, None]).astype(x.dtype)
        with jax.named_scope("gmm"):
            da = _dot(do, w2, 1, 1).astype(x.dtype)
            dwm = _add_to_group(dwm, g, _dot(a, do, 0, 0))
        with jax.named_scope("moe_act"):
            dh = _gate_bwd(act, gate, up, da)
        with jax.named_scope("gmm"):
            drows = _dot(dh, w1, 1, 1)
            dwi = _add_to_group(dwi, g, _dot(rows, dh, 0, 0))
        with jax.named_scope("moe_route"):
            return (scatter_add_rows(dx, idx, n_live[i], drows), dscale,
                    dwi, dwm)

    dx, dscale, dwi, dwm = lax.fori_loop(0, n_blocks, visit, tuple(
        _zeros_like_of(x, shape, jnp.float32) for shape in (
            carry_shape(*x.shape), scale.shape, wi.shape, wm.shape)))
    with jax.named_scope("moe_route"):
        dweights = jnp.where(here, dscale[dest], 0).reshape(len(x), -1)
    return (rows_of(dx, x.shape[1], x.dtype), dweights,
            dwi.astype(wi.dtype),
            dwm.astype(wm.dtype), None)


def _gate_bwd(act: str, gate, up, da):
    """``[d gate, d up]`` of ``act(gate) * up`` given its cotangent ``da``,
    in ``gate``'s dtype; SwiGLU's derivative in float32: silu'(g) =
    sigmoid(g) (1 + g (1 - sigmoid(g)))."""
    if act == "reglu":
        return jnp.concatenate([jnp.where(gate > 0, da * up, 0),
                                da * jax.nn.relu(gate)], axis=1)
    g, u, d = (t.astype(jnp.float32) for t in (gate, up, da))
    sg = jax.nn.sigmoid(g)
    return jnp.concatenate([d * u * sg * (1 + g * (1 - sg)), d * g * sg],
                           axis=1).astype(gate.dtype)


_expert_rows.defvjp(_expert_rows_fwd, _expert_rows_bwd)


def expert_ffn(x, ids, weights, wi, wm, held: Tuple[int, int],
               block_rows: int = 512, act: str = "reglu"):
    """The held experts' part of a routed feed-forward without dropped
    tokens.  ``x`` [n, D]; ``ids``, ``weights`` [n, k] from
    :func:`route_topk`; ``wi`` [G, D, 2 F] (gate then up) and ``wm``
    [G, F, D] the weights of experts ``held[0] <= id < held[0] + G``.
    -> ``sum over a token's held experts e of w_e wm_e (act(gate_e x) *
    (up_e x))`` [n, D], ``act`` relu ("reglu") or silu ("swiglu").  An
    assignment to an expert held elsewhere adds nothing.

    There is no capacity: the row buffer has room for every assignment
    (n k rows) and a block of padding a held expert, but only its integer
    arrays and the rows' scales are ever made.  The held assignments lie
    sorted by expert, each expert's group padded to a whole number of
    blocks, so a block holds rows of one expert, and a pass is ONE loop
    over the blocks in use: a visit gathers the block's tokens' rows,
    multiplies them by the expert's two weights with the gate between
    (operands in ``x``'s dtype, sums in float32) and adds the scaled result
    into the tokens in float32, the block's live rows alone, by
    ``ops.row_scatter.scatter_add_rows`` (a block's live rows are a prefix
    of it and their tokens differ: a token has one row an expert).  The
    backward (one ``custom_vjp``) keeps the inputs, each block's count of
    live rows and no row of the buffer: a visit makes the block's rows and
    products again, then their cotangents.  So the work follows the
    rows held and less than a block an expert more, and under a
    checkpoint nothing of the forward loop has to run again."""
    G = wi.shape[0]
    if held[1] != G:
        raise ValueError(f"told to hold {held[1]} experts, given {G}")
    block = min(block_rows, ids.size)
    with jax.named_scope("moe_route"):
        sizes, _, tok, which, live, dest, here = row_buffer(
            ids, len(x), held, block)
        n_blocks, group = _visits(sizes, tok.shape[0], block)[:2]
        n_live = jnp.sum(live.reshape(-1, block), axis=1, dtype=jnp.int32)
    return _expert_rows(x, weights, wi, wm,
                        (n_blocks, group, tok, n_live, which, live, dest,
                         here), block, act)


def dropless_moe_ffn(params: dict, x, *, experts_per_token: int,
                     held: Optional[Tuple[int, int]] = None,
                     router_input=None, dtype: Any = None,
                     block_rows: int = 512):
    """An expert layer without dropped tokens that is told which experts
    it holds.  ``params``: ``router`` [D, E] over ALL experts, ``wi``
    [G, D, 2 F] and ``wm`` [G, F, D] of the G experts held here, ids
    ``held[0] .. held[0] + G - 1`` (default: all E).  ``x`` [..., D] is what
    the experts read; ``router_input`` what the router reads where that is
    another tensor (a router placed before attention), default ``x``.
    Returns the held experts' contribution, no residual, in ``x``'s dtype:
    :func:`route_topk` then :func:`expert_ffn` under the scope ``moe``.
    Experts run in ``dtype`` (default ``x``'s), the router in float32."""
    G = params["wi"].shape[0]
    held = held or (0, G)
    lead, D = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, D)
    rt = xt if router_input is None else router_input.reshape(-1, D)
    with jax.named_scope("moe"):
        ids, weights = route_topk(rt, params["router"], experts_per_token)
        y = expert_ffn(xt.astype(dtype or x.dtype), ids, weights,
                       params["wi"], params["wm"], held, block_rows)
    return y.astype(x.dtype).reshape(*lead, D)
