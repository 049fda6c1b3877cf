"""The layers of a SambaY decoder-hybrid-decoder (arXiv:2507.06607, built on
Samba, arXiv:2406.07522), as ``models/gpt.py``'s ``layer_stack`` runs them
where a configuration names them (``GPTConfig.operator`` "mamba", "diff",
"gmu", "cross"), beside its own "attn" and "conv" layers.

Every layer is ``x += mixer(N1 x)``, then ``x += FFN(N2 x)`` (the FFN
residual of ``models/gpt.py``), the norms ``GPTConfig.norm``'s.  The
mixers, on ``h = N1 x`` [B, T, D]:

- **mamba** (Mamba-1): ``[u, z] = h W_in``; ``u' = silu(conv(u) + b)``, a
  depthwise causal convolution of ``d_conv`` taps; ``[dlt, B, C] = u' W_x``;
  ``delta = softplus(dlt W_dt + b_dt)``, ``A = -exp(A_log)``; the selective
  scan (``ops/selective_scan.py``) ``y``; ``m = (y + D u') silu(z)``;
  ``m W_out``, with Mamba-1's sizes (``D_STATE``, ``D_CONV``, ``EXPAND``,
  :func:`dt_rank`).  ``m`` is the layer's gated scan output: the **memory**
  where the layer is the last mamba layer before the first gmu layer.
- **diff**, differential attention (arXiv:2410.05258):
  ``q = [q1 | q2]`` (``n_heads`` heads of ``head_dim``,
  two halves), ``k = [k1 | k2]`` (``kv_heads``), ``v`` of ``kv_heads / 2``
  heads of ``2 head_dim``;
  ``o = (softmax(q1 k1^T / sqrt(Dh)) - lam softmax(q2 k2^T / sqrt(Dh))) v``
  with ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init``; then
  ``(1 - lam_init) RMSNorm(o)`` over each head and ``o W_o``.  The two maps
  run as ONE flash call, stacked on the head axis (``v`` repeated onto its
  two k heads).  The layer's K and V are the **shared KV** where it is the
  last diff layer before the first cross layer; no positions (NoPE).
- **cross**: the same attention with q projected and K, V the shared KV
  (full causal).
- **gmu** (gated memory unit): ``(m silu(h W_g)) W_o``.

A layer here is ``fn(layer, x, carry) -> (x, carry)``: ``carry`` holds the
memory and the shared KV from the layers that make them to those that read
them, and gradients flow back along it.  Scopes: ``ssm_proj`` (the norm and
a Mamba layer's two products ``W_in``, ``W_out``), ``ssm`` (the rest of the
Mamba mixer, the scan kernels among it), ``gmu``, ``attn`` (norm,
projections, the flash call) and ``diff_mix`` (lam, the difference and the
sub-norm).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

OPERATORS = ("mamba", "diff", "gmu", "cross")
# Mamba-1's sizes: the state a channel, the convolution's taps, the inner
# width over the model's
D_STATE, D_CONV, EXPAND = 16, 4, 2

F32 = jnp.float32


def dt_rank(cfg) -> int:
    """The rank of a mamba layer's step projection: Mamba-1's
    ``ceil(d_model / 16)``."""
    return -(-cfg.d_model // 16)


def _last_before(cfg, kind: str, reader: str):
    """The last layer of ``kind`` before the first of ``reader``, or None."""
    ops = [cfg.layer_operator(i) for i in range(cfg.n_layers)]
    if reader not in ops:
        return None
    before = [i for i in range(ops.index(reader)) if ops[i] == kind]
    return before[-1] if before else None


def memory_layer(cfg):
    """The mamba layer whose gated scan output the gmu layers read."""
    return _last_before(cfg, "mamba", "gmu")


def kv_layer(cfg):
    """The diff layer whose K and V the cross layers read."""
    return _last_before(cfg, "diff", "cross")


def lambda_init(cfg, index: int) -> float:
    """Differential attention's ``lam_init`` at the layer's published depth
    (``GPTConfig.first_layer`` + ``index``)."""
    return 0.8 - 0.6 * math.exp(-0.3 * (cfg.first_layer + index))


def kind(cfg, index: int) -> tuple:
    """What makes layer ``index``'s function differ from another's."""
    op = cfg.layer_operator(index)
    return (op, cfg.layer_window(index), index == memory_layer(cfg),
            index == kv_layer(cfg),
            lambda_init(cfg, index) if op in ("diff", "cross") else None)


def init_layer(keys, cfg, index: int) -> dict:
    """The mixer's f32 weights of layer ``index`` (``keys`` an iterator of
    PRNG keys): normal over sqrt(fan-in), and the family's own starts for
    ``A_log`` (log 1..N), the step's bias (inverse softplus of a
    log-uniform draw in [1e-3, 1e-1]), ``D`` (one) and the lam vectors
    (N(0, 0.1))."""
    D, Dh, H, Hkv = cfg.d_model, cfg.head_dim, cfg.n_heads, cfg.kv_heads
    E, N, R, K = EXPAND * D, D_STATE, dt_rank(cfg), D_CONV

    def dense(shape, fan_in):
        return jax.random.normal(next(keys), shape, F32) / np.sqrt(fan_in)

    op = cfg.layer_operator(index)
    if op == "mamba":
        dt = jnp.exp(jax.random.uniform(next(keys), (E,), F32)
                     * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return dict(w_in=dense((D, 2 * E), D), conv=dense((K, E), K),
                    conv_b=jnp.zeros((E,), F32), w_x=dense((E, R + 2 * N), E),
                    w_dt=dense((R, E), R), dt_b=dt + jnp.log(-jnp.expm1(-dt)),
                    a_log=jnp.log(jnp.broadcast_to(
                        jnp.arange(1, N + 1, dtype=F32), (E, N))),
                    d_skip=jnp.ones((E,), F32), w_out=dense((E, D), E))
    if op == "gmu":
        return dict(w_g=dense((D, E), D), w_o=dense((E, D), E))
    out = dict(wq=dense((D, H, Dh), D), wo=dense((H // 2, 2 * Dh, D),
                                                 H * Dh))
    if op == "diff":
        out.update(wk=dense((D, Hkv, Dh), D),
                   wv=dense((D, Hkv // 2, 2 * Dh), D))
    out.update({name: 0.1 * jax.random.normal(next(keys), (Dh,), F32)
                for name in ("lam_q1", "lam_k1", "lam_q2", "lam_k2")},
               subln=jnp.ones((2 * Dh,), F32))
    return out


def _mamba(layer, h, cfg):
    """(the mixer's output [B, T, D], the gated scan output m [B, T, E])."""
    from ..ops.selective_scan import selective_scan
    N, R = D_STATE, dt_rank(cfg)
    T = h.shape[1]
    with jax.named_scope("ssm_proj"):
        uz = jnp.einsum("btd,de->bte", h, layer["w_in"].astype(cfg.dtype))
    with jax.named_scope("ssm"):
        u, z = jnp.split(uz, 2, -1)
        u, taps = u.astype(F32), layer["conv"].astype(F32)
        c = taps[0] * u + layer["conv_b"]
        for j in range(1, taps.shape[0]):
            c = c + taps[j] * jnp.pad(u, ((0, 0), (j, 0), (0, 0)))[:, :T]
        u = jax.nn.silu(c)
        xdbc = jnp.einsum("bte,er->btr", u.astype(cfg.dtype),
                          layer["w_x"].astype(cfg.dtype),
                          preferred_element_type=F32)
        dlt, B, C = jnp.split(xdbc, [R, R + N], -1)
        delta = jax.nn.softplus(jnp.einsum(
            "btr,re->bte", dlt.astype(cfg.dtype),
            layer["w_dt"].astype(cfg.dtype), preferred_element_type=F32)
            + layer["dt_b"])
        # the scan's output in the activations' dtype: what remat keeps
        y = selective_scan(u, delta, -jnp.exp(layer["a_log"]), B, C,
                           out_dtype=cfg.dtype)
        m = ((y + layer["d_skip"] * u)
             * jax.nn.silu(z.astype(F32))).astype(cfg.dtype)
    with jax.named_scope("ssm_proj"):
        return jnp.einsum("bte,ed->btd", m,
                          layer["w_out"].astype(cfg.dtype)), m


def _gmu(layer, h, m, cfg):
    with jax.named_scope("gmu"):
        g = jax.nn.silu(jnp.einsum("btd,de->bte", h,
                                   layer["w_g"].astype(cfg.dtype)))
        return jnp.einsum("bte,ed->btd", m * g,
                          layer["w_o"].astype(cfg.dtype))


def _diff_attention(layer, h, cfg, index, attn, kv=None):
    """(the mixer's output, the layer's (k, v)): ``kv`` given, the layer
    projects q alone and attends to it (cross-attention)."""
    from .gpt import _attend
    half, window = cfg.n_heads // 2, cfg.layer_window(index)
    with jax.named_scope("attn"):
        proj = lambda w: jnp.einsum("btd,dhk->bthk", h,
                                    layer[w].astype(cfg.dtype))
        q = proj("wq")
        k, v = (proj("wk"), proj("wv")) if kv is None else kv
        o = _attend(q, k, jnp.concatenate([v, v], axis=2), attn, None,
                    kv_groups=cfg.kv_groups, window=window)
    with jax.named_scope("diff_mix"):
        dot = lambda a, b: jnp.exp(jnp.sum(layer[a] * layer[b]))
        lam0 = lambda_init(cfg, index)
        lam = dot("lam_q1", "lam_k1") - dot("lam_q2", "lam_k2") + lam0
        o = o[:, :, :half].astype(F32) - lam * o[:, :, half:].astype(F32)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5)
        o = (o * layer["subln"] * (1 - lam0)).astype(cfg.dtype)
    with jax.named_scope("attn"):
        return jnp.einsum("bthk,hkd->btd", o,
                          layer["wo"].astype(cfg.dtype)), (k, v)


def apply_layer(layer, x, carry, cfg, *, index: int, attn: str):
    """Layer ``index`` on ``x`` [B, T, D] -> (x, carry); ``carry`` a dict
    that gains ``"memory"`` at :func:`memory_layer` and ``"kv"``
    at :func:`kv_layer`."""
    from .gpt import _ffn_residual, _norm
    op = cfg.layer_operator(index)
    scope = {"mamba": "ssm_proj", "gmu": "gmu"}.get(op, "attn")
    with jax.named_scope(scope):
        h = _norm(x, layer, "ln1", cfg)
    carry = dict(carry)
    if op == "mamba":
        out, m = _mamba(layer, h, cfg)
        if index == memory_layer(cfg):
            carry["memory"] = m
    elif op == "gmu":
        out = _gmu(layer, h, carry["memory"], cfg)
    else:
        out, kv = _diff_attention(layer, h, cfg, index, attn,
                                  carry["kv"] if op == "cross" else None)
        if index == kv_layer(cfg):
            carry["kv"] = kv
    return _ffn_residual(layer, x + out, cfg), carry


def check(cfg) -> None:
    """What a configuration of these layers has to hold; raises by name."""
    ops = [cfg.layer_operator(i) for i in range(cfg.n_layers)]
    if cfg.n_experts:
        raise ValueError("mamba, diff, gmu and cross layers feed a dense "
                         "feed-forward: no experts")
    differential = [i for i, op in enumerate(ops) if op in ("diff", "cross")]
    if any(cfg.layer_rope(i) for i in differential):
        raise ValueError("diff and cross layers attend without positions: "
                         "no RoPE on them")
    if differential and (cfg.kv_heads % 2 or cfg.n_heads % 2):
        raise ValueError("differential attention splits the query and KV "
                         "heads in two halves: both counts must be even")
    if "gmu" in ops and memory_layer(cfg) is None:
        raise ValueError("a gmu layer reads a memory: a mamba layer must "
                         "come before the first gmu layer")
    if "cross" in ops and kv_layer(cfg) is None:
        raise ValueError("a cross layer reads a shared KV: a diff layer "
                         "must come before the first cross layer")
