"""Flash attention as Pallas TPU kernels — forward AND backward.

The hot op of the transformer models: blockwise online-softmax attention
computed in VMEM, grid (batch, heads, q-blocks, k-blocks) with the k-block
dimension innermost so the accumulator scratch carries across k-steps —
the canonical TPU flash pattern (see /opt/skills/guides/pallas_guide.md,
"Scratch Memory" + "Common Pitfalls").

Inputs are [B, T, H, D].  The MXU sees [block_q, D] x [D, block_k] and
[block_q, block_k] x [block_k, D] matmuls with
``preferred_element_type=f32``; bf16 inputs are upcast per block.

The backward is FlashAttention-2 style: the forward also emits the
log-sum-exp rows (stored lane-replicated as [B, H, T, 128] to satisfy the
TPU (8, 128) tiling of block shapes — same convention as jax's reference
TPU kernel); the backward recomputes ``p = exp(q k^T s - lse)`` per block
and accumulates

    dv += p^T dO,   ds = p * (dO v^T - delta),   dk += ds^T q * s,
    dq += ds k * s,        with  delta = rowsum(dO * O)

in ONE kernel (q innermost; dk/dv in block accumulators, dq in a [T, D]
f32 scratch that holds the whole sequence's dq of one (batch, head)) where
that scratch fits its VMEM budget (:func:`_fused_backward`: T <= 8192 at
heads of 128), so each tile's p and ds are made once: five matmuls a tile.
Longer sequences run two kernels (dq with k innermost beside dk/dv with q
innermost: seven matmuls a tile).  ``delta`` is precomputed once per row
by a tiny kernel of its own (lane-replicated like lse), so training
memory stays O(T * D) — no [T, T] materialization anywhere.

Causal grids step over the tiles above the diagonal without running or
fetching them: the index maps (:func:`_k_block_index`,
:func:`_q_block_index`) name the neighbouring visible block again.

On CPU (tests, CI) the kernels run with ``interpret=True``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128  # TPU lane width: row-stat buffers are [bq, 128]
# The kernels run softmax in BASE-2: exp2 is the TPU's native
# transcendental (exp lowers to exp2 + a per-element multiply), so
# folding log2(e) INTO the score scale removes one full VPU pass over
# every [bq, bk] tile.  Measured on v5e (B4 T2048, non-causal): fwd
# 42.7 -> 48.8 TFLOP/s at head_dim 64 and 74.3 -> 88.7 at head_dim 128
# — the hd128 kernel reaches its own no-softmax matmul ceiling.
# Externally visible lse stays in NATURAL log units.
_LOG2E = 1.4426950408889634
_INV_LOG2E = 1.0 / _LOG2E


def _causal_tile_classes(iq, ik, block_q, block_k, window=None):
    """Classify tile (iq, ik) against the causal diagonal — the single
    source of truth for all the kernels (fwd, bwd-dq, bwd-dkv).
    Returns (below, on_diag, visible): ``below`` = every key position in
    the tile visible to every query (no mask needed), ``on_diag`` =
    straddles the diagonal (mask required), ``visible`` = any pair
    visible.

    ``window``: query i sees key j only where ``i - j < window`` as well.
    A tile wholly left of that band is not visible; one that straddles the
    band's left edge needs the mask like one on the diagonal."""
    q_lo = iq * block_q
    q_hi = q_lo + block_q - 1
    k_lo = ik * block_k
    k_hi = k_lo + block_k - 1
    visible = k_lo <= q_hi
    below = k_hi <= q_lo
    if window is None:
        return below, visible & (k_hi > q_lo), visible
    visible = visible & (q_lo - k_hi < window)
    below = below & (q_hi - k_lo < window)
    return below, visible & jnp.logical_not(below), visible


def _causal_dispatch(body, causal, iq, ik, block_q, block_k, window=None):
    """Run ``body(masked=...)`` once per visible tile: fully-visible
    tiles skip the mask iota/compare/select, only the tiles that straddle
    the diagonal (or the left edge of ``window``'s band) pay it.  Blocks
    strictly above the diagonal or left of the band run nothing — their
    grid steps are predicated off."""
    if not causal:
        body(masked=False)
        return
    below, on_diag, _ = _causal_tile_classes(iq, ik, block_q, block_k,
                                             window)

    @pl.when(below)
    def _():
        body(masked=False)

    @pl.when(on_diag)
    def _():
        body(masked=True)


def _k_block_index(causal, block_q, block_k, window=None):
    """The k-side block a grid step (iq, ik) asks for.  Causal: never a
    block above the diagonal — a step that runs nothing names the last
    visible k block of its q row again, and a repeated index is not
    fetched again (the dead step costs a grid step and no DMA).  With a
    ``window`` never one left of the band either: those steps name the
    row's first visible k block."""
    if not causal:
        return lambda iq, ik: ik
    if window is None:
        return lambda iq, ik: jnp.minimum(
            ik, (iq * block_q + block_q - 1) // block_k)
    return lambda iq, ik: jnp.clip(
        ik, jnp.maximum(iq * block_q - window + 1, 0) // block_k,
        (iq * block_q + block_q - 1) // block_k)


def _q_block_index(causal, block_q, block_k, n_q, window=None):
    """The q-side block (q, dO, lse, delta) a grid step (iq, ik) asks
    for; causal: the first visible q block of the k column in place of
    one above the diagonal (clamped: with T_k > T_q a k column may see
    no q row at all).  With a ``window`` the column's last visible q block
    in place of one past the band."""
    if not causal:
        return lambda iq, ik: iq
    if window is None:
        return lambda iq, ik: jnp.minimum(
            jnp.maximum(iq, ik * block_k // block_q), n_q - 1)
    return lambda iq, ik: jnp.clip(
        iq, jnp.minimum(ik * block_k // block_q, n_q - 1),
        jnp.minimum((ik * block_k + block_k + window - 2) // block_q,
                    n_q - 1))


def _mask_tile(s, iq, ik, block_q, block_k, window):
    """Scores of tile (iq, ik) with the pairs no query may see at NEG_INF:
    keys after the query, and with a ``window`` keys ``window`` or more
    positions before it."""
    qpos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    if window is None:
        return jnp.where(qpos >= kpos, s, NEG_INF)
    return jnp.where((qpos >= kpos) & (qpos - kpos < window), s, NEG_INF)


# ------------------------------------------------------------------ forward
def _fa_kernel(q_ref, k_ref, v_ref, o_ref, *rest, causal, scale, block_q,
               block_k, n_k, with_lse, window=None):
    if with_lse:
        lse_ref, acc, m, l = rest
    else:
        acc, m, l = rest
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, NEG_INF)
        l[...] = jnp.zeros_like(l)

    def _attend(masked: bool):
        # MXU eats the native (bf16) dtype; accumulation is f32 via
        # preferred_element_type — upcasting inputs first would force the
        # slow multi-pass f32 MXU path.  Softmax runs in BASE-2 with
        # log2(e) folded into the score scale (see _LOG2E above): the
        # probabilities 2^(s*scale*log2e - m) equal e^(s*scale - m/log2e)
        # exactly, and one VPU multiply pass over the tile disappears.
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32
                                ) * (scale * _LOG2E)
        if masked:
            s = _mask_tile(s, iq, ik, block_q, block_k, window)
        m_prev = m[:, :1]
        s_max = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, s_max)
        p = jnp.exp2(s - m_new)
        corr = jnp.exp2(m_prev - m_new)
        l[...] = jnp.broadcast_to(
            corr * l[:, :1] + jnp.sum(p, axis=1, keepdims=True), l.shape)
        m[...] = jnp.broadcast_to(m_new, m.shape)
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _causal_dispatch(_attend, causal, iq, ik, block_q, block_k, window)

    @pl.when(ik == n_k - 1)
    def _finish():
        lsafe = jnp.maximum(l[:, :1], 1e-30)
        o_ref[0, 0, :, :] = (acc[...] / lsafe).astype(o_ref.dtype)
        if with_lse:
            # m is a base-2 max of scaled scores; emit NATURAL-log lse
            # (the ring-flash merge statistic and the backward expect it)
            lse_ref[0, 0, :, :] = jnp.broadcast_to(
                m[:, :1] * _INV_LOG2E + jnp.log(lsafe), lse_ref.shape[2:])


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying ``like``'s varying-axis (vma) type, so the
    kernels compose with shard_map's check_vma (e.g. flash attention on
    each shard inside a dp/tp mesh)."""
    vma = getattr(jax.typeof(like), "vma", None)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def fit_block(T: int, requested: int) -> int:
    """Largest usable block size <= requested for sequence length T: a
    divisor of T that is a multiple of 8 (the TPU sublane tile), or T
    itself when T <= requested.  Raises when no such divisor exists."""
    b = min(requested, T)
    if T % b == 0:
        return b
    for cand in range(b - b % 8, 7, -8):
        if T % cand == 0:
            return cand
    raise ValueError(
        f"sequence length {T} has no block divisor that is a multiple "
        f"of 8 (pad the sequence)")


def _block_sizes(T, Tk, block_q, block_k):
    return fit_block(T, block_q), fit_block(Tk, block_k)


def _flash_forward(q, k, v, causal: bool, block_q: int, block_k: int,
                   interpret: bool, with_lse: bool, window=None):
    """``with_lse`` is set only on the VJP path — the primal would just
    discard the [B, H, T, 128] residual (HBM allocation + write).
    ``window`` (static, causal only): see :func:`flash_attention`."""
    B, T, H, D = q.shape
    Tk = k.shape[1]
    block_q, block_k = _block_sizes(T, Tk, block_q, block_k)
    n_q, n_k = T // block_q, Tk // block_k
    scale = 1.0 / np.sqrt(D)

    # kernels run in [B, H, T, D] layout so blocks tile the (T, D) plane
    # (the TPU (8, 128) constraint); the boundary transposes fuse into the
    # surrounding projection einsums
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    kernel = functools.partial(_fa_kernel, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k, n_k=n_k,
                               with_lse=with_lse, window=window)
    o_spec = pl.BlockSpec((1, 1, block_q, D),
                          lambda b, h, iq, ik: (b, h, iq, 0))
    k_at = _k_block_index(causal, block_q, block_k, window)
    k_spec = pl.BlockSpec((1, 1, block_k, D),
                          lambda b, h, iq, ik: (b, h, k_at(iq, ik), 0))
    out_specs = [o_spec]
    out_shape = [_sds(qt.shape, qt.dtype, q)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, 1, block_q, _LANES),
                                      lambda b, h, iq, ik: (b, h, iq, 0)))
        out_shape.append(_sds((B, H, T, _LANES), jnp.float32, q))
    res = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[o_spec, k_spec, k_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qt, kt, vt)
    out = res[0]
    lse = res[1] if with_lse else None
    return jnp.transpose(out, (0, 2, 1, 3)), lse


# ----------------------------------------------------------------- backward
def _fa_delta_kernel(o_ref, do_ref, delta_ref):
    """delta = rowsum(dO * O), stored lane-replicated like lse — computed
    once per q row instead of once per (q-block, k-block) pair."""
    o = o_ref[0, 0, :, :].astype(jnp.float32)
    do = do_ref[0, 0, :, :].astype(jnp.float32)
    d = jnp.sum(o * do, axis=1, keepdims=True)
    delta_ref[0, 0, :, :] = jnp.broadcast_to(d, delta_ref.shape[2:])


def _block_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *, masked,
                scale, block_q, block_k, iq, ik, window=None):
    """Recompute p and ds for one (q-block, k-block) pair, all f32.
    Base-2 like the forward: p = 2^(s*scale*log2e - lse*log2e).
    ``masked`` is True only for causal blocks straddling the diagonal —
    fully-visible blocks skip the iota/compare/select passes (see the
    forward kernel)."""
    q = q_ref[0, 0, :, :]
    k = k_ref[0, 0, :, :]
    v = v_ref[0, 0, :, :]
    do = do_ref[0, 0, :, :]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32
                            ) * (scale * _LOG2E)
    if masked:
        s = _mask_tile(s, iq, ik, block_q, block_k, window)
    lse = lse_ref[0, 0, :, :1] * _LOG2E                   # [bq, 1], base-2
    p = jnp.exp2(s - lse)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    delta = delta_ref[0, 0, :, :1]                        # [bq, 1]
    ds = p * (dp - delta) * scale
    return p, ds, q, do


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dq_acc, *, causal, scale, block_q, block_k,
                      n_k, window=None):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _accum(masked: bool):
        _, ds, _, _ = _block_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                  delta_ref, masked=masked, scale=scale,
                                  block_q=block_q, block_k=block_k,
                                  iq=iq, ik=ik, window=window)
        k = k_ref[0, 0, :, :]
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _causal_dispatch(_accum, causal, iq, ik, block_q, block_k, window)

    @pl.when(ik == n_k - 1)
    def _finish():
        dq_ref[0, 0, :, :] = dq_acc[...].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       *rest, causal, scale, block_q, block_k, n_q, n_k,
                       with_dq, window=None):
    """dk and dv of one k block, q innermost.  ``with_dq``: the fused
    backward — the tile's ``ds`` also adds its q rows' ``ds k`` into a
    [T, D] f32 scratch that holds the whole sequence's dq of this
    (batch, head), so p and ds are made once a tile (five matmuls, not
    the seven of a dq kernel beside this one)."""
    if with_dq:
        dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    ik = pl.program_id(2)
    iq = pl.program_id(3)  # q innermost: accumulators carry across q-blocks

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if with_dq:
        @pl.when((ik == 0) & (iq == 0))
        def _init_dq():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    def _accum(masked: bool):
        p, ds, q, do = _block_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                   delta_ref, masked=masked, scale=scale,
                                   block_q=block_q, block_k=block_k,
                                   iq=iq, ik=ik, window=window)
        ds = ds.astype(q.dtype)
        # dv += p^T dO ; dk += ds^T q
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if with_dq:
            rows = pl.ds(pl.multiple_of(iq * block_q, block_q), block_q)
            dq_acc[rows, :] += jax.lax.dot_general(
                ds, k_ref[0, 0, :, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    _causal_dispatch(_accum, causal, iq, ik, block_q, block_k, window)

    @pl.when(iq == n_q - 1)
    def _finish():
        dk_ref[0, 0, :, :] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[...].astype(dv_ref.dtype)

    if with_dq:
        @pl.when((ik == n_k - 1) & (iq == n_q - 1))
        def _finish_dq():
            dq_ref[0, 0, :, :] = dq_acc[...].astype(dq_ref.dtype)


# The fused backward keeps one (batch, head)'s whole dq in VMEM as f32,
# [T, D] with D padded to the lane width, beside the tiles.  Within this
# budget dq, dk and dv come from ONE kernel (T <= 8192 at D <= 128, 4096
# at D = 256); past it the dq kernel runs beside the dk/dv kernel, as for
# every shape before.  The fused call needs more scoped VMEM than the
# 16 MiB default (refused on v5e by 56 KB at T 4096, D 128): 34.9 MiB
# at the budget's edge with f32 operands (T 4096, D 256), so it asks for
# 48 of the v5e's 128; 24 to 100 MiB time alike (PERF.md section 6).
_FUSED_DQ_BYTES = 4 * 1024 * 1024
_FUSED_VMEM_LIMIT = 48 * 1024 * 1024


def _fused_backward(T: int, D: int) -> bool:
    return T * -(-D // _LANES) * _LANES * 4 <= _FUSED_DQ_BYTES


def _flash_backward(q, k, v, out, lse, g, causal, block_q, block_k,
                    interpret, dlse=None, window=None):
    """``dlse`` (optional, [B, H, T] f32): cotangent of the lse output.
    It folds into the per-row term of ``ds`` — mathematically
    d lse/d s = p, so ds picks up ``+ p * dlse`` exactly where the delta
    correction subtracts (FA2 with lse gradient, as needed by ring-flash
    merging)."""
    B, T, H, D = q.shape
    Tk = k.shape[1]
    block_q, block_k = _block_sizes(T, Tk, block_q, block_k)
    n_q, n_k = T // block_q, Tk // block_k
    scale = 1.0 / np.sqrt(D)

    # the residual arrives slim ([B, H, T] — storing it lane-replicated
    # across fwd→bwd would cost 128x HBM per layer); re-expand to the
    # kernel's [B, H, T, LANES] row layout only for this backward
    lse = jnp.broadcast_to(lse[..., None], (B, H, T, _LANES))

    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    ot = jnp.transpose(out, (0, 2, 1, 3))
    gt = jnp.transpose(g, (0, 2, 1, 3))

    # delta preprocess: one rowsum per q row (vs per block pair)
    dspec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq: (b, h, iq, 0))
    delta = pl.pallas_call(
        _fa_delta_kernel,
        grid=(B, H, n_q),
        in_specs=[dspec, dspec],
        out_specs=pl.BlockSpec((1, 1, block_q, _LANES),
                               lambda b, h, iq: (b, h, iq, 0)),
        out_shape=_sds((B, H, T, _LANES), jnp.float32, q),
        interpret=interpret,
        name="flash_bwd_delta",
    )(ot, gt)
    if dlse is not None:
        # ds = p * (dp - delta + dlse) * scale — fold dlse into the row term
        delta = delta - jnp.broadcast_to(
            dlse.astype(jnp.float32)[..., None], delta.shape)

    # q innermost for dk/dv: k/v block indexed by grid axis 2
    q_at = _q_block_index(causal, block_q, block_k, n_q, window)
    kq_spec = pl.BlockSpec((1, 1, block_q, D),
                           lambda b, h, ik, iq: (b, h, q_at(iq, ik), 0))
    kk_spec = pl.BlockSpec((1, 1, block_k, D),
                           lambda b, h, ik, iq: (b, h, ik, 0))
    krow_spec = pl.BlockSpec((1, 1, block_q, _LANES),
                             lambda b, h, ik, iq: (b, h, q_at(iq, ik), 0))
    out_specs = [kk_spec, kk_spec]
    out_shape = [_sds(kt.shape, kt.dtype, k), _sds(vt.shape, vt.dtype, v)]
    scratch = [pltpu.VMEM((block_k, D), jnp.float32),
               pltpu.VMEM((block_k, D), jnp.float32)]
    fused = _fused_backward(T, D)
    if fused:
        # dq rides in the dk/dv kernel: one block for the whole sequence,
        # resident across the (ik, iq) steps of a (batch, head)
        out_specs.insert(0, pl.BlockSpec(
            (1, 1, T, D), lambda b, h, ik, iq: (b, h, 0, 0)))
        out_shape.insert(0, _sds(qt.shape, qt.dtype, q))
        scratch.insert(0, pltpu.VMEM((T, D), jnp.float32))
    # the fused kernel keeps the dk/dv kernel's name: the benchmark's
    # flash_bwd_ms.* read /flash_bwd_(delta|dq|dkv)/ (docs/monitoring.md,
    # "Scope names")
    outs = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k, n_q=n_q,
                          n_k=n_k, with_dq=fused, window=window),
        grid=(B, H, n_k, n_q),
        in_specs=[kq_spec, kk_spec, kk_spec, kq_spec, krow_spec, krow_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=(pltpu.CompilerParams(
            vmem_limit_bytes=_FUSED_VMEM_LIMIT) if fused else None),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qt, kt, vt, gt, lse, delta)
    dk, dv = outs[-2:]
    if fused:
        dq = outs[0]
    else:
        k_at = _k_block_index(causal, block_q, block_k, window)
        q_spec = pl.BlockSpec((1, 1, block_q, D),
                              lambda b, h, iq, ik: (b, h, iq, 0))
        k_spec = pl.BlockSpec((1, 1, block_k, D),
                              lambda b, h, iq, ik: (b, h, k_at(iq, ik), 0))
        row_spec = pl.BlockSpec((1, 1, block_q, _LANES),
                                lambda b, h, iq, ik: (b, h, iq, 0))
        dq = pl.pallas_call(
            functools.partial(_fa_bwd_dq_kernel, causal=causal, scale=scale,
                              block_q=block_q, block_k=block_k, n_k=n_k,
                              window=window),
            grid=(B, H, n_q, n_k),
            in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
            out_specs=q_spec,
            out_shape=_sds(qt.shape, qt.dtype, q),
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
            interpret=interpret,
            name="flash_bwd_dq",
        )(qt, kt, vt, gt, lse, delta)
    back = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    return back(dq), back(dk), back(dv)


def _auto_interpret() -> bool:
    return jax.default_backend() == "cpu"


def _use_jnp_fallback(q) -> bool:
    """Interpret-mode Pallas can't run under a vma-tracking shard_map
    (its internal scratch ops mix varying/invarying states), so on CPU
    inside shard_map we compute with an equivalent jnp path instead.  On
    TPU the real kernels run everywhere (verified in-shard on hardware);
    direct CPU calls still exercise the kernels via interpret=True."""
    return _auto_interpret() and bool(getattr(jax.typeof(q), "vma", ()))


def _jnp_flash(q, k, v, causal, window=None):
    """Differentiable jnp twin of the kernel: (out, lse [B, H, T] f32)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) / np.sqrt(q.shape[-1])
    if causal:
        Tq, Tk = s.shape[2], s.shape[3]
        mask = jnp.arange(Tq)[:, None] >= jnp.arange(Tk)[None, :]
        if window is not None:
            mask = mask & (jnp.arange(Tq)[:, None] - jnp.arange(Tk)[None, :]
                           < window)
        s = jnp.where(mask[None, None], s, NEG_INF)
    m = jax.lax.stop_gradient(jnp.max(s, axis=-1))
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p / l[..., None],
                     v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype), m + jnp.log(l)


def _expand_kv_heads(t, kv_groups: int):
    """[B, T, Hkv, D] -> [B, T, Hkv*g, D] (repeat: query head h reads KV
    head h // g, matching models.gpt._expand_kv)."""
    return t if kv_groups == 1 else jnp.repeat(t, kv_groups, axis=2)


def _compact_kv_grad(dt, kv_groups: int):
    """Adjoint of _expand_kv_heads: sum each group's gradients."""
    if kv_groups == 1:
        return dt
    B, T, H, D = dt.shape
    return dt.reshape(B, T, H // kv_groups, kv_groups, D).sum(axis=3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention_pallas(q, k, v, causal, block_q, block_k, kv_groups,
                            bwd_blocks, window=None):
    out, _ = _flash_forward(q, _expand_kv_heads(k, kv_groups),
                            _expand_kv_heads(v, kv_groups), causal,
                            block_q, block_k, _auto_interpret(),
                            with_lse=False, window=window)
    return out


def _fa_fwd(q, k, v, causal, block_q, block_k, kv_groups, bwd_blocks,
            window=None):
    out, lse = _flash_forward(q, _expand_kv_heads(k, kv_groups),
                              _expand_kv_heads(v, kv_groups), causal,
                              block_q, block_k, _auto_interpret(),
                              with_lse=True, window=window)
    # what the kernel made carries a name, so that a checkpoint policy
    # around the caller (gpt.layer_stack's under remat="full") can keep it
    # and spare the backward a second flash_fwd; the primal output and the
    # residual are the same named value, and outside a checkpoint region a
    # name is the identity
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse[..., 0], "flash_lse")
    # residuals keep k/v COMPACT under GQA — the expand re-runs in the
    # backward (a cheap repeat) instead of storing kv_groups-times the
    # KV activations across the whole fwd->bwd window
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, block_q, block_k, kv_groups, bwd_blocks, window, res,
            g):
    q, k, v, out, lse = res
    bq, bk = bwd_blocks or (block_q, block_k)
    dq, dk, dv = _flash_backward(q, _expand_kv_heads(k, kv_groups),
                                 _expand_kv_heads(v, kv_groups), out, lse,
                                 g, causal, bq, bk,
                                 _auto_interpret(), window=window)
    return (dq, _compact_kv_grad(dk, kv_groups),
            _compact_kv_grad(dv, kv_groups))


_flash_attention_pallas.defvjp(_fa_fwd, _fa_bwd)


def _big_tile_ok() -> bool:
    """Whether the 16 MiB f32 2048x2048 probability tile is known to fit
    this target's VMEM.  Measured-good on v5e ("TPU v5 lite") ONLY;
    every other generation falls back to 1024 until measured (a too-big
    default would turn a working config into a compile failure)."""
    kind = jax.devices()[0].device_kind.lower()
    return "v5 lite" in kind or "v5e" in kind


def default_blocks(head_dim: int, seq_len: int):
    """Forward block sizes by (head_dim, seq), measured on v5e:

    - head_dim 64: 1024x1024 (1.7x faster than 512x512; the [bq, bk]
      probability tile is the VMEM budget — 4 MiB f32 at 1024x1024 —
      and bigger tiles amortize the grid/revisit overhead).
    - head_dim >= 128 at seq <= 2048: 2048x2048 — the whole sequence in
      ONE tile fits VMEM and measures fwd 51.6 vs 40.8 TFLOP/s,
      lifting the fwd+bwd composite 56.9 -> 74.3 TFLOP/s (+31%) with
      the backward held at 1024 (its budget — two f32 tiles + two
      accumulators, and in the fused kernel the sequence's dq — overflows
      the 16 MiB default at 2048; with the fused call's 48 MiB a 2048
      tile compiles and is slower: 4.1 ms a call of 32 heads at 4096
      with (2048, 1024) and 6.7 with (2048, 2048) against 3.6 with
      (1024, 1024), and (512, *) 3.7).  At longer sequences the
      multi-k-block 2048-tile lse-saving forward overflows VMEM
      (measured 24.0M vs the 16M budget at seq 8192), so 1024 stands.
      Gated on targets where the 16 MiB tile is measured to fit
      (:func:`_big_tile_ok`).

    Shorter sequences fall back via fit_block either way."""
    if head_dim >= 128 and seq_len <= 2048 and _big_tile_ok():
        return (2048, 2048)
    return (1024, 1024)


_BWD_BLOCKS_CAP = 1024   # backward VMEM budget ceiling (see above)


def flash_attention(q, k, v, causal: bool = False,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None, kv_groups: int = 1,
                    bwd_blocks=None, window: Optional[int] = None):
    """Pallas flash attention, [B, T, H, D] → [B, T, H, D].

    ``window`` (static, needs ``causal``): query i sees keys j with
    ``0 <= i - j < window``.  Tiles wholly left of that band are neither
    fetched nor computed, tiles on its left edge are masked; ``None`` is
    plain causal attention, the same kernels as before the argument.

    ``kv_groups > 1``: GQA — ``k``/``v`` arrive compact ([B, T, H/g, D])
    and are expanded inside the VJP so the saved residuals stay compact.

    ``block_q``/``block_k`` default by head_dim (:func:`default_blocks`);
    ``bwd_blocks``: optional (block_q, block_k) for the backward
    kernels, whose VMEM budget (two f32 tiles + the accumulators) is
    tighter — it defaults to the forward blocks capped at 1024.
    """
    if block_q is None or block_k is None:
        # gate on the LONGER side: block_k tiles k's sequence, and the
        # VMEM overflow the docstring describes is a k-block count effect
        dq, dk = default_blocks(q.shape[-1],
                                max(q.shape[1], k.shape[1]))
        block_q = block_q or dq
        block_k = block_k or dk
    if bwd_blocks is None:
        bwd_blocks = (min(block_q, _BWD_BLOCKS_CAP),
                      min(block_k, _BWD_BLOCKS_CAP))
    if window is not None and (not causal or window < 1):
        raise ValueError(f"window={window} needs causal=True and at least "
                         f"one visible position")
    if _use_jnp_fallback(q):
        return _jnp_flash(q, _expand_kv_heads(k, kv_groups),
                          _expand_kv_heads(v, kv_groups), causal, window)[0]
    return _flash_attention_pallas(q, k, v, causal, block_q, block_k,
                                   kv_groups, bwd_blocks, window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_with_lse_pallas(q, k, v, causal, block_q, block_k, kv_groups):
    out, lse = _flash_forward(q, _expand_kv_heads(k, kv_groups),
                              _expand_kv_heads(v, kv_groups), causal,
                              block_q, block_k, _auto_interpret(),
                              with_lse=True)
    return out, lse[..., 0]


def _fal_fwd(q, k, v, causal, block_q, block_k, kv_groups):
    out, lse = _flash_forward(q, _expand_kv_heads(k, kv_groups),
                              _expand_kv_heads(v, kv_groups), causal,
                              block_q, block_k, _auto_interpret(),
                              with_lse=True)
    return (out, lse[..., 0]), (q, k, v, out, lse[..., 0])


def _fal_bwd(causal, block_q, block_k, kv_groups, res, g):
    q, k, v, out, lse = res
    do, dlse = g
    dq, dk, dv = _flash_backward(q, _expand_kv_heads(k, kv_groups),
                                 _expand_kv_heads(v, kv_groups), out, lse,
                                 do, causal, block_q, block_k,
                                 _auto_interpret(), dlse=dlse)
    return (dq, _compact_kv_grad(dk, kv_groups),
            _compact_kv_grad(dv, kv_groups))


_flash_with_lse_pallas.defvjp(_fal_fwd, _fal_bwd)


def flash_attention_with_lse(q, k, v, causal: bool = False,
                             block_q: int = 1024, block_k: int = 1024,
                             kv_groups: int = 1):
    """Like :func:`flash_attention` but also returns the per-row
    log-sum-exp ``[B, H, T]`` (f32) — the merge statistic for combining
    partial attentions over KV chunks (ring-flash).  Both outputs are
    differentiable: the lse cotangent folds into the backward's row term.
    ``kv_groups``: see :func:`flash_attention`.
    """
    if _use_jnp_fallback(q):
        return _jnp_flash(q, _expand_kv_heads(k, kv_groups),
                          _expand_kv_heads(v, kv_groups), causal)
    return _flash_with_lse_pallas(q, k, v, causal, block_q, block_k,
                                  kv_groups)
