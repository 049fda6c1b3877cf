"""Chunked-vocab softmax cross-entropy: LM loss without the logits tensor.

Training a causal LM the plain way materializes ``[B, T, V]`` float32
logits — at seq 8192 x vocab 32768 that is 1 GiB per sequence (8 GiB for
a batch of 8), usually the single largest training buffer.  This op
computes

    loss[b, t] = logsumexp_v(x[b, t] @ W[:, v]) - x[b, t] @ W[:, y[b, t]]

by scanning the vocab in chunks with an online logsumexp (the same
max/sum-rescale trick flash attention uses along sequence), so peak
memory is ``[B, T, chunk]``.  The backward pass recomputes each chunk's
logits and accumulates ``dx`` and ``dW`` chunk by chunk (custom VJP —
rematerialization over the vocab axis).

Chunk matmuls run on the MXU via ``preferred_element_type=float32`` with
bf16 inputs kept bf16.  No reference analogue (the reference stops at
BERT-sized fixtures); this extends the flagship GPT family the same way
``ops/flash_attention.py`` does for the attention op.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["chunked_cross_entropy"]


def _num_chunks(V: int, chunk: int) -> int:
    if V % chunk:
        raise ValueError(f"vocab {V} not divisible by chunk {chunk}; "
                         f"pad the embedding table or pick a divisor")
    return V // chunk


def _chunk_logits(x, w, c, chunk):
    """f32 logits of vocab chunk ``c``: [B, T, chunk].  Inputs stay in
    their native dtype (bf16 feeds the MXU directly); only the product
    accumulates in f32."""
    wc = lax.dynamic_slice_in_dim(w, c * chunk, chunk, axis=1)
    return jnp.einsum("btd,dv->btv", x, wc,
                      preferred_element_type=jnp.float32)


def _target_logit(x, w, targets):
    """x[b,t] . W[:, y[b,t]] without any [B,T,V] product: gather the
    target columns ([D, B, T]) and contract over D in f32."""
    wt = jnp.take(w, targets, axis=1)  # [D, B, T]
    return jnp.einsum("btd,dbt->bt", x, wt,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def chunked_cross_entropy(x, w, targets, chunk: int = 8192):
    """Per-token CE loss [B, T] for features ``x`` [B, T, D], head ``w``
    [D, V], integer targets [B, T].  ``chunk`` divides V.

    ``w`` must be the FULL (unsharded) head and ``targets`` global vocab
    ids — there is no tensor-parallel support here; under tp use
    models.gpt.parallel_cross_entropy, which reduces over the vocab
    shards.  Out-of-range target ids are not checked (XLA gathers clamp
    silently)."""
    loss, _ = _fwd(x, w, targets, chunk)
    return loss


def _online_lse(x, w, chunk):
    """Scan the vocab chunks, carrying the running (max, sumexp)."""
    n = _num_chunks(w.shape[1], chunk)
    # derive the carries from x so they inherit its varying/manual axes
    # when traced inside shard_map (a literal jnp.full carry would not)
    s0 = jnp.zeros_like(x[..., 0], dtype=jnp.float32)
    m0 = s0 - jnp.inf

    def body(carry, c):
        m, s = carry
        lg = _chunk_logits(x, w, c, chunk)
        mc = jnp.max(lg, axis=-1)
        mn = jnp.maximum(m, mc)
        s = s * jnp.exp(m - mn) + jnp.sum(jnp.exp(lg - mn[..., None]),
                                          axis=-1)
        return (mn, s), None

    (m, s), _ = lax.scan(body, (m0, s0), jnp.arange(n))
    return m + jnp.log(s)


@jax.named_scope("ce_head")
def _fwd(x, w, targets, chunk):
    lse = _online_lse(x, w, chunk)
    loss = lse - _target_logit(x, w, targets)
    return loss, (x, w, targets, lse)


# a custom_vjp's backward is traced apart from its forward, so the scope
# is opened a second time
@jax.named_scope("ce_head")
def _bwd(chunk, res, g):
    x, w, targets, lse = res
    B, T, D = x.shape
    V = w.shape[1]
    n = _num_chunks(V, chunk)
    gx = g[..., None]  # [B, T, 1]

    def body(carry, c):
        dx_acc, dw_acc = carry
        lg = _chunk_logits(x, w, c, chunk)              # recompute
        p = jnp.exp(lg - lse[..., None]) * gx           # [B, T, chunk]
        wc = lax.dynamic_slice_in_dim(w, c * chunk, chunk, axis=1)
        dx_acc = dx_acc + jnp.einsum("btv,dv->btd", p, wc,
                                     preferred_element_type=jnp.float32)
        dwc = jnp.einsum("btd,btv->dv", x, p,
                         preferred_element_type=jnp.float32)
        dw_acc = lax.dynamic_update_slice_in_dim(
            dw_acc, dwc.astype(dw_acc.dtype), c * chunk, axis=1)
        return (dx_acc, dw_acc), None

    dx0 = jnp.zeros_like(x, dtype=jnp.float32)
    dw0 = jnp.zeros_like(w, dtype=jnp.float32)
    (dx, dw), _ = lax.scan(body, (dx0, dw0), jnp.arange(n))

    # subtract the target-column term: d/dlogit[y] = -1
    wt = jnp.take(w, targets, axis=1)                      # [D, B, T]
    dx = dx - jnp.einsum("bt,dbt->btd", g, wt,
                         preferred_element_type=jnp.float32)
    flat_tgt = targets.reshape(-1)
    flat_xg = (x.astype(jnp.float32) * gx).reshape(-1, D)  # [B*T, D]
    dw = dw.at[:, flat_tgt].add(-flat_xg.T)
    return dx.astype(x.dtype), dw.astype(w.dtype), None


chunked_cross_entropy.defvjp(_fwd, _bwd)
