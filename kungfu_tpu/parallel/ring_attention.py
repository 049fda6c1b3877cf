"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

Long-context scaling on TPU.  Sequences are sharded over a mesh axis; the
two classic schedules are provided:

- **Ring attention**: KV shards circulate around the ring via
  ``lax.ppermute`` while each device accumulates its queries' attention
  over every chunk with the online-softmax (flash) recurrence.  Peak
  memory is O(T/n) per device and the ppermute overlaps with the block
  compute inside one XLA program over ICI.
- **Ulysses**: ``lax.all_to_all`` re-shards from sequence-sharded to
  head-sharded, runs dense local attention, and re-shards back.  Cheaper
  for moderate sequence lengths when heads >= ring size.

The reference framework has no sequence axis (SURVEY.md §5 "long-context:
absent") — this is a TPU-native extension, not reference parity; it rides
the same mesh/collective substrate as the DP engine.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attend(q, k, v, acc, m, l, bias):
    """One online-softmax accumulation step (flash recurrence).

    q: [B, Tq, H, D]; k, v: [B, Tk, H, D]; acc: [B, Tq, H, D];
    m, l: [B, Tq, H] running max / normalizer; bias: [Tq, Tk] additive.
    """
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = s + bias[None, None, :, :]
    s_max = jnp.max(s, axis=-1)                      # [B, H, Tq]
    m_new = jnp.maximum(m, s_max.transpose(0, 2, 1))  # [B, Tq, H]
    p = jnp.exp(s - m_new.transpose(0, 2, 1)[:, :, :, None])  # [B,H,Tq,Tk]
    corr = jnp.exp(m - m_new)                        # [B, Tq, H]
    l_new = corr * l + jnp.sum(p, axis=-1).transpose(0, 2, 1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    acc_new = acc * corr[:, :, :, None] + pv
    return acc_new, m_new, l_new


def _expand_groups(t, groups: int):
    """[B, T, Hkv, D] -> [B, T, Hkv*groups, D] (GQA head expansion)."""
    return t if groups == 1 else jnp.repeat(t, groups, axis=2)


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   kv_groups: int = 1):
    """Blockwise ring attention over sequence shards.

    Must run inside ``shard_map`` over ``axis_name``.  All of q, k, v are
    the local sequence shard ``[B, T_local, H, D]``; the global sequence is
    the concatenation over ranks in rank order.  Returns the local output
    shard ``[B, T_local, H, D]``.

    ``kv_groups`` > 1 (GQA): k/v carry only ``H / kv_groups`` heads — the
    COMPACT form rotates around the ring (kv_groups-times less inter-chip
    traffic) and is expanded just-in-time for each local block compute.
    """
    n = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    T = q.shape[1]
    perm = [(i, (i + 1) % n) for i in range(n)]

    q_pos = rank * T + jnp.arange(T)                 # global query positions

    qf = q.astype(jnp.float32)
    # init derived from qf so the carry is axis-varying under shard_map
    acc = qf * 0.0
    m = qf[..., 0] * 0.0 + NEG_INF
    l = qf[..., 0] * 0.0

    def body(step, carry):
        acc, m, l, kc, vc = carry
        # current chunk originated at rank - step (mod n)
        src = (rank - step + n) % n
        k_pos = src * T + jnp.arange(T)
        if causal:
            bias = jnp.where(q_pos[:, None] >= k_pos[None, :], 0.0, NEG_INF)
        else:
            bias = jnp.zeros((T, T), jnp.float32)
        acc, m, l = _block_attend(
            qf, _expand_groups(kc, kv_groups).astype(jnp.float32),
            _expand_groups(vc, kv_groups).astype(jnp.float32),
            acc, m, l, bias)
        # rotate KV around the ring (skippable on the last step, but a
        # static ppermute inside scan keeps the schedule uniform)
        kc = lax.ppermute(kc, axis_name, perm=perm)
        vc = lax.ppermute(vc, axis_name, perm=perm)
        return acc, m, l, kc, vc

    acc, m, l, _, _ = lax.fori_loop(0, n, body, (acc, m, l, k, v))
    # causal: every query row has attended at least its own position → l > 0
    out = acc / jnp.maximum(l, 1e-30)[:, :, :, None]
    return out.astype(q.dtype)


def ring_flash_attention(q, k, v, axis_name: str, causal: bool = False,
                         block_q: int = 512, block_k: int = 512,
                         kv_groups: int = 1):
    """Ring attention whose per-chunk compute is the Pallas flash kernel.

    Same semantics and layout as :func:`ring_attention` (inside shard_map,
    local shards [B, T_local, H, D], global sequence = rank-order concat),
    but each (queries x KV-chunk) block runs on the MXU via
    ``flash_attention_with_lse`` and partial results merge with the
    numerically-stable log-sum-exp combine.  Gradients flow through the
    kernel's custom VJP (the lse cotangent folds into its row term) and
    through ``ppermute``'s transpose — the backward ring is generated
    by AD.

    Chunk visibility under ``causal``: step 0 is the diagonal chunk
    (causal mask inside the kernel); at step s the incoming chunk
    originated at ``rank - s``, which is entirely in the past when
    ``rank >= s`` (full attention) and entirely in the future otherwise
    (merged with weight zero).
    """
    n = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    from ..ops.flash_attention import flash_attention_with_lse

    # KV stays COMPACT end-to-end under GQA: transported compact over the
    # ring AND handed to the kernel compact (its VJP expands internally
    # and keeps compact residuals) — kv_groups-times less inter-chip
    # traffic and saved-activation memory per chunk
    o0, lse0 = flash_attention_with_lse(q, k, v, causal, block_q, block_k,
                                        kv_groups=kv_groups)
    acc = o0.astype(jnp.float32)
    lse_acc = lse0                       # [B, H, T_local] f32

    def step(carry, s):
        acc, lse_acc, kc, vc = carry
        kc = lax.ppermute(kc, axis_name, perm=perm)
        vc = lax.ppermute(vc, axis_name, perm=perm)
        oi, lsei = flash_attention_with_lse(
            q, kc, vc, False, block_q, block_k, kv_groups=kv_groups)
        if causal:
            # wrapped chunks (src rank > this rank) are future: weight 0
            lsei = jnp.where(rank >= s, lsei, NEG_INF)
        lse_new = jnp.logaddexp(lse_acc, lsei)
        w_old = jnp.exp(lse_acc - lse_new)               # [B, H, T]
        w_new = jnp.exp(lsei - lse_new)
        tohd = lambda w: jnp.transpose(w, (0, 2, 1))[..., None]
        acc = acc * tohd(w_old) + oi.astype(jnp.float32) * tohd(w_new)
        return (acc, lse_new, kc, vc), None

    if n > 1:
        (acc, _, _, _), _ = lax.scan(step, (acc, lse_acc, k, v),
                                     jnp.arange(1, n))
    return acc.astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str, causal: bool = False,
                      kv_groups: int = 1):
    """All-to-all (Ulysses/DeepSpeed-style) sequence parallelism.

    Inside ``shard_map``: re-shard [B, T/n, H, D] → [B, T, H/n, D] with one
    ``all_to_all``, run dense local attention on full sequences for the
    local head group, then re-shard back.  Requires H % n == 0.

    ``kv_groups`` > 1 (GQA): the compact k/v go through the all_to_all
    (kv_groups-times less traffic; needs kv_heads % n == 0) and expand
    after re-sharding.
    """
    n = lax.axis_size(axis_name)
    if q.shape[2] % n != 0:
        raise ValueError(f"heads {q.shape[2]} not divisible by ring {n}")
    if k.shape[2] % n != 0:
        raise ValueError(f"kv heads {k.shape[2]} not divisible by ring {n}")

    def to_heads(x):   # [B, T/n, H, D] -> [B, T, H/n, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def to_seq(x):     # [B, T, H/n, D] -> [B, T/n, H, D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh = to_heads(q)
    kh = _expand_groups(to_heads(k), kv_groups)
    vh = _expand_groups(to_heads(v), kv_groups)
    out = reference_attention(qh, kh, vh, causal=causal)
    return to_seq(out)


def reference_attention(q, k, v, causal: bool = False, window=None):
    """Dense softmax attention — the correctness oracle and the local
    kernel inside Ulysses.  [B, T, H, D] layout.  ``window`` (with
    ``causal``): a query sees only the ``window`` newest positions, itself
    included."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        Tq, Tk = s.shape[2], s.shape[3]
        mask = jnp.arange(Tq)[:, None] >= jnp.arange(Tk)[None, :]
        if window is not None:
            mask = mask & (jnp.arange(Tq)[:, None] - jnp.arange(Tk)[None, :]
                           < window)
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _seq_specs(axis: str):
    return P(None, axis, None, None)


def make_ring_attention(mesh: Mesh, axis: str = "sp",
                        causal: bool = False):
    """Jitted [B, T, H, D] attention with T sharded over ``mesh[axis]``."""
    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name=axis, causal=causal),
        mesh=mesh, in_specs=(_seq_specs(axis),) * 3,
        out_specs=_seq_specs(axis))
    return jax.jit(fn)


def make_ulysses_attention(mesh: Mesh, axis: str = "sp",
                           causal: bool = False):
    """Jitted [B, T, H, D] attention, Ulysses schedule."""
    fn = jax.shard_map(
        functools.partial(ulysses_attention, axis_name=axis, causal=causal),
        mesh=mesh, in_specs=(_seq_specs(axis),) * 3,
        out_specs=_seq_specs(axis))
    return jax.jit(fn)


def make_ring_flash_attention(mesh: Mesh, axis: str = "sp",
                              causal: bool = False,
                              block_q: int = 512, block_k: int = 512):
    """Jitted [B, T, H, D] ring attention with Pallas flash chunks."""
    fn = jax.shard_map(
        functools.partial(ring_flash_attention, axis_name=axis,
                          causal=causal, block_q=block_q, block_k=block_k),
        mesh=mesh, in_specs=(_seq_specs(axis),) * 3,
        out_specs=_seq_specs(axis))
    return jax.jit(fn)
