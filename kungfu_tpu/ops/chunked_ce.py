"""Chunked-vocab softmax cross-entropy: LM loss without the logits tensor.

Training a causal LM the plain way materializes ``[B, T, V]`` float32
logits — at seq 8192 x vocab 32768 that is 1 GiB per sequence (8 GiB for
a batch of 8), usually the single largest training buffer.  This op
computes

    loss[b, t] = logsumexp_v(x[b, t] @ W[:, v]) - x[b, t] @ W[:, y[b, t]]

by scanning the vocab in chunks with an online logsumexp (the same
max/sum-rescale trick flash attention uses along sequence), so peak
memory is ``[B, T, chunk]``.  The target's logit is picked out of the
chunk that holds it by comparing column numbers, in the pass that sums
the exponentials: no gather.  The backward pass (custom VJP —
rematerialization over the vocab axis) recomputes each chunk's logits,
takes the one off the target's column of the softmax by the same
comparison, and gets from it the chunk's share of ``dx``, summed in f32,
and the chunk's columns of ``dW``, final at once and returned in ``W``'s
dtype: no scatter and no full-width f32 gradient buffer.

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


def _chunk_of(w, c, chunk):
    """Columns [c * chunk, (c + 1) * chunk) of the head."""
    return lax.dynamic_slice_in_dim(w, c * chunk, chunk, axis=1)


def _chunk_logits(x, w, c, chunk):
    """f32 logits of vocab chunk ``c``: [B, T, chunk].  Inputs stay in
    their native dtype (bf16 feeds the MXU directly); only the product
    accumulates in f32."""
    return jnp.einsum("btd,dv->btv", x, _chunk_of(w, c, chunk),
                      preferred_element_type=jnp.float32)


def _hit(targets, c, chunk):
    """Where chunk ``c`` holds each token's target column: bool
    [B, T, chunk], by comparison (no gather).  A target outside [0, V)
    is hit in no chunk."""
    return (targets[..., None] - c * chunk) == jnp.arange(chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def chunked_cross_entropy(x, w, targets, chunk: int = 8192):
    """Per-token CE loss [B, T] for features ``x`` [B, T, D], head ``w``
    [D, V], integer targets [B, T].  ``chunk`` divides V.

    ``w`` must be the FULL (unsharded) head and ``targets`` global vocab
    ids — there is no tensor-parallel support here; under tp use
    models.gpt.parallel_cross_entropy, which reduces over the vocab
    shards.  Out-of-range target ids are not checked: such a token has
    no target term, so its loss is the bare logsumexp and its gradient
    the softmax's."""
    loss, _ = _fwd(x, w, targets, chunk)
    return loss


def _online_lse(x, w, targets, chunk):
    """Scan the vocab chunks, carrying the running (max, sumexp) and the
    target's logit, picked out of the chunk that holds it in the same
    pass over the chunk's logits."""
    n = _num_chunks(w.shape[1], chunk)
    # derive the carries from x so they inherit its varying/manual axes
    # when traced inside shard_map (a literal jnp.full carry would not)
    s0 = jnp.zeros_like(x[..., 0], dtype=jnp.float32)
    m0 = s0 - jnp.inf

    def body(carry, c):
        m, s, t = carry
        lg = _chunk_logits(x, w, c, chunk)
        mc = jnp.max(lg, axis=-1)
        mn = jnp.maximum(m, mc)
        s = s * jnp.exp(m - mn) + jnp.sum(jnp.exp(lg - mn[..., None]),
                                          axis=-1)
        t = t + jnp.sum(jnp.where(_hit(targets, c, chunk), lg, 0.0),
                        axis=-1)
        return (mn, s, t), None

    (m, s, t), _ = lax.scan(body, (m0, s0, s0), jnp.arange(n))
    return m + jnp.log(s), t


@jax.named_scope("ce_head")
def _fwd(x, w, targets, chunk):
    lse, tgt = _online_lse(x, w, targets, chunk)
    return lse - tgt, (x, w, targets, lse)


# a custom_vjp's backward is traced apart from its forward, so the scope
# is opened a second time
@jax.named_scope("ce_head")
def _bwd(chunk, res, g):
    x, w, targets, lse = res
    n = _num_chunks(w.shape[1], chunk)
    gx = g[..., None]  # [B, T, 1]

    # Unrolled over the static chunk count, not scanned: a chunk's dW is
    # final when its matmul ends, so it is cast to w's dtype there and
    # joined to the others by whatever reads dW.  A scan would stack the
    # chunks on a new leading axis (a transposing copy away from [D, V])
    # or carry a full-width buffer it has to fill and copy into.
    dx = jnp.zeros_like(x, dtype=jnp.float32)
    dws = []
    for c in range(n):
        lg = _chunk_logits(x, w, c, chunk)              # recompute
        # d loss / d logits: the softmax, less one at the target's column
        p = (jnp.exp(lg - lse[..., None])
             - _hit(targets, c, chunk).astype(jnp.float32)) * gx
        dx = dx + jnp.einsum("btv,dv->btd", p, _chunk_of(w, c, chunk),
                             preferred_element_type=jnp.float32)
        dws.append(jnp.einsum("btd,btv->dv", x, p,
                              preferred_element_type=jnp.float32
                              ).astype(w.dtype))
    return dx.astype(x.dtype), jnp.concatenate(dws, axis=1), None


chunked_cross_entropy.defvjp(_fwd, _bwd)
