"""The selective scan of a Mamba layer as Pallas TPU kernels, forward and
backward, under one ``custom_vjp``.

``selective_scan(u, delta, A, B, C)`` computes, for each sequence and each
channel c, with a state ``s`` of N entries a channel,

    s_t = exp(delta_t A) * s_{t-1} + (delta_t u_t) B_t        s_0 = 0
    y_t = sum_n C_t[n] s_t[n]

on ``u``, ``delta`` [b, T, ch] float32, ``A`` [ch, N] (negative), ``B``,
``C`` [b, T, N] float32 -> ``y`` [b, T, ch] float32.  The skip ``D u`` and
the gate ``silu(z)`` are the caller's (elementwise, XLA fuses them).

Layout.  A grid step holds one block of channels and one chunk of positions:
the state ``[N, block]`` in a float32 VMEM scratch (N on sublanes, channels
on lanes), carried across the chunks of a sequence, which run innermost.
``u`` and ``delta`` come as ``[chunk, block]`` tiles, positions on sublanes;
``B`` and ``C`` come transposed, ``[N, chunk]``, so position t's ``[N, 1]``
column is picked from the tile by a lane select and a lane sum.  Positions
go eight at a time: one aligned ``[8, block]`` load of each input and one
store of ``y``.

The forward also writes the state at each chunk's start (``[b, chunks, N,
ch]``, 1/chunk of the inputs' bytes times N).  The backward walks the
chunks in reverse: it makes the chunk's states again from its start state
(kept in VMEM, ``[chunk, N, block]``), then walks the positions back with
the state's cotangent ``g``:

    g_t  = C_t dy_t + exp(delta_{t+1} A) g_{t+1}
    du_t = delta_t sum_n g_t B_t
    ddelta_t = sum_n g_t (exp(delta_t A) A s_{t-1} + B_t u_t)
    dA  += g_t exp(delta_t A) s_{t-1} delta_t
    dB_t = sum_c g_t delta_t u_t,   dC_t = sum_c dy_t s_t

``dB`` and ``dC`` come out one ``[N, T]`` a channel block and are summed
after the call.

The Mosaic calls are named ``ssm_scan_fwd`` and ``ssm_scan_bwd``; the
benchmark's ``ssm_scan_ms.*`` reads them by name.  On CPU the calls run
interpreted, by ``ops.flash_attention._auto_interpret``; inside a
``shard_map`` that checks varying axes the interpreter cannot run (its VMEM
varies over no mesh axis), so there, on the CPU alone, the scan is
:func:`scan_reference`, a ``lax.scan`` over the positions, as the flash
kernels are their jnp twin there.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention

_LANES, _SUBLANES = 128, 8
CHUNK = 128     # positions a grid step: B and C's [N, chunk] tiles are lanes
BLOCK = 512     # channels a grid step: the state is [N, 512] f32, 8 vregs
# the backward keeps a chunk's states, [128, 16, 512] f32 (4 MiB), beside
# double-buffered [128, 512] tiles of six arrays
_VMEM_LIMIT = 32 * 1024 * 1024


def _fit(n: int, want: int, unit: int) -> int:
    """The block of ``n`` a step takes: ``want`` where it divides, else a
    divisor that is a multiple of ``unit``, else all of ``n``."""
    if n <= want:
        return n
    for cand in range(want - want % unit, 0, -unit):
        if n % cand == 0:
            return cand
    return n


def blocks(T: int, ch: int):
    """(chunk, block): positions and channels a grid step."""
    if T % _SUBLANES:
        raise ValueError(f"the scan takes positions eight at a time: "
                         f"{T} is not a multiple of {_SUBLANES}")
    return _fit(T, CHUNK, _LANES), _fit(ch, BLOCK, _LANES)


def _column(tile, lane):
    """[N, chunk] tile -> its column ``lane`` (dynamic), [N, 1]."""
    at = lax.broadcasted_iota(jnp.int32, tile.shape, 1) == lane
    return jnp.sum(jnp.where(at, tile, 0.0), axis=1, keepdims=True)


def _sds(shape, like):
    """A float32 output's shape, varying over the axes ``like`` varies
    over (flash_attention._sds)."""
    return flash_attention._sds(shape, jnp.float32, like)


def _place_row(rows, row, j):
    """``rows`` [8, block] with its row ``j`` (static) set to ``row``."""
    at = lax.broadcasted_iota(jnp.int32, rows.shape, 0) == j
    return jnp.where(at, row, rows)


# ------------------------------------------------------------------ forward
def _fwd_kernel(u_ref, d_ref, a_ref, b_ref, c_ref, y_ref, h_ref, state, *,
                chunk):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    h_ref[0, 0] = state[...]
    A, bt, ct = a_ref[...], b_ref[0], c_ref[0]

    def group(g, s):
        t0 = pl.multiple_of(g * _SUBLANES, _SUBLANES)
        d8 = d_ref[0, pl.ds(t0, _SUBLANES), :]
        du8 = d8 * u_ref[0, pl.ds(t0, _SUBLANES), :]
        y8 = jnp.zeros_like(d8)
        for j in range(_SUBLANES):
            s = (jnp.exp(d8[j:j + 1] * A) * s
                 + _column(bt, t0 + j) * du8[j:j + 1])
            y8 = _place_row(y8, jnp.sum(s * _column(ct, t0 + j), axis=0,
                                        keepdims=True), j)
        y_ref[0, pl.ds(t0, _SUBLANES), :] = y8
        return s

    state[...] = lax.fori_loop(0, chunk // _SUBLANES, group, state[...])


def _scan_forward(u, delta, At, Bt, Ct, interpret):
    """y [b, T, ch] and the chunks' start states [b, chunks, N, ch]."""
    b, T, ch = u.shape
    N = At.shape[0]
    chunk, block = blocks(T, ch)
    n_chunks = T // chunk
    seq = pl.BlockSpec((1, chunk, block), lambda i, c, k: (i, k, c))
    bc = pl.BlockSpec((1, N, chunk), lambda i, c, k: (i, 0, k))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk),
        grid=(b, ch // block, n_chunks),
        in_specs=[seq, seq, pl.BlockSpec((N, block), lambda i, c, k: (0, c)),
                  bc, bc],
        out_specs=[seq, pl.BlockSpec((1, 1, N, block),
                                     lambda i, c, k: (i, k, 0, c))],
        out_shape=[_sds((b, T, ch), u), _sds((b, n_chunks, N, ch), u)],
        scratch_shapes=[pltpu.VMEM((N, block), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="ssm_scan_fwd",
    )(u, delta, At, Bt, Ct)


# ----------------------------------------------------------------- backward
def _bwd_kernel(u_ref, d_ref, a_ref, b_ref, c_ref, h_ref, dy_ref,
                du_ref, dd_ref, da_ref, db_ref, dc_ref, states, grad, *,
                chunk):
    first = pl.program_id(2) == 0

    @pl.when(first)
    def _():
        grad[...] = jnp.zeros_like(grad)
        da_ref[0] = jnp.zeros(da_ref.shape[1:], jnp.float32)

    A, bt, ct = a_ref[...], b_ref[0], c_ref[0]
    groups = chunk // _SUBLANES

    # the chunk's states again, from its start: states[t] = s_{t-1}
    def again(g, s):
        t0 = pl.multiple_of(g * _SUBLANES, _SUBLANES)
        d8 = d_ref[0, pl.ds(t0, _SUBLANES), :]
        du8 = d8 * u_ref[0, pl.ds(t0, _SUBLANES), :]
        for j in range(_SUBLANES):
            states[t0 + j] = s
            s = (jnp.exp(d8[j:j + 1] * A) * s
                 + _column(bt, t0 + j) * du8[j:j + 1])
        return s

    # the start state goes through the scratch, whose loads vary over no
    # mesh axis as the loop's other values do not either
    states[0] = h_ref[0, 0]
    lax.fori_loop(0, groups, again, states[0])

    def back(k, carry):
        g_next, dA, db, dc = carry
        t0 = pl.multiple_of((groups - 1 - k) * _SUBLANES, _SUBLANES)
        d8 = d_ref[0, pl.ds(t0, _SUBLANES), :]
        u8 = u_ref[0, pl.ds(t0, _SUBLANES), :]
        dy8 = dy_ref[0, pl.ds(t0, _SUBLANES), :]
        du8, dd8 = jnp.zeros_like(d8), jnp.zeros_like(d8)
        lanes = lax.broadcasted_iota(jnp.int32, bt.shape, 1)
        for j in reversed(range(_SUBLANES)):
            t = t0 + j
            d, u, dy = d8[j:j + 1], u8[j:j + 1], dy8[j:j + 1]
            bcol, ccol = _column(bt, t), _column(ct, t)
            prev = states[t]
            a = jnp.exp(d * A)
            s = a * prev + bcol * (d * u)
            g = g_next + ccol * dy
            gb = jnp.sum(g * bcol, axis=0, keepdims=True)
            ga = g * a * prev
            du8 = _place_row(du8, gb * d, j)
            dd8 = _place_row(dd8, jnp.sum(ga * A, axis=0, keepdims=True)
                             + gb * u, j)
            dA = dA + ga * d
            db = jnp.where(lanes == t, jnp.sum(g * (d * u), axis=1,
                                               keepdims=True), db)
            dc = jnp.where(lanes == t, jnp.sum(dy * s, axis=1,
                                               keepdims=True), dc)
            g_next = a * g
        du_ref[0, pl.ds(t0, _SUBLANES), :] = du8
        dd_ref[0, pl.ds(t0, _SUBLANES), :] = dd8
        return g_next, dA, db, dc

    zero = jnp.zeros(bt.shape, jnp.float32)
    g, dA, db, dc = lax.fori_loop(
        0, groups, back, (grad[...], jnp.zeros_like(A), zero, zero))
    grad[...] = g
    da_ref[0] += dA
    db_ref[0, 0] = db
    dc_ref[0, 0] = dc


def _scan_backward(u, delta, At, Bt, Ct, h, dy, interpret):
    b, T, ch = u.shape
    N = At.shape[0]
    chunk, block = blocks(T, ch)
    n_chunks, n_blocks = T // chunk, ch // block
    last = n_chunks - 1
    seq = pl.BlockSpec((1, chunk, block), lambda i, c, k: (i, last - k, c))
    bc = pl.BlockSpec((1, N, chunk), lambda i, c, k: (i, 0, last - k))
    part = pl.BlockSpec((1, 1, N, chunk), lambda i, c, k: (i, c, 0, last - k))
    du, dd, dA, dB, dC = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk),
        grid=(b, n_blocks, n_chunks),
        in_specs=[seq, seq, pl.BlockSpec((N, block), lambda i, c, k: (0, c)),
                  bc, bc,
                  pl.BlockSpec((1, 1, N, block),
                               lambda i, c, k: (i, last - k, 0, c)),
                  seq],
        out_specs=[seq, seq,
                   pl.BlockSpec((1, N, block), lambda i, c, k: (i, 0, c)),
                   part, part],
        out_shape=[_sds((b, T, ch), u), _sds((b, T, ch), u),
                   _sds((b, N, ch), u), _sds((b, n_blocks, N, T), u),
                   _sds((b, n_blocks, N, T), u)],
        scratch_shapes=[pltpu.VMEM((chunk, N, block), jnp.float32),
                        pltpu.VMEM((N, block), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="ssm_scan_bwd",
    )(u, delta, At, Bt, Ct, h, dy)
    return du, dd, dA.sum(0).T, dB.sum(1), dC.sum(1)


# ------------------------------------------------------------------ the op
def _transposed(t):
    return jnp.swapaxes(t, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(u, delta, A, B, C, out_dtype):
    return _scan_forward(u, delta, A.T, _transposed(B), _transposed(C),
                         flash_attention._auto_interpret())[0].astype(
                             out_dtype)


def _scan_fwd(u, delta, A, B, C, out_dtype):
    y, h = _scan_forward(u, delta, A.T, _transposed(B), _transposed(C),
                         flash_attention._auto_interpret())
    # named, so that a checkpoint policy around the caller (gpt.layer_stack
    # under remat="full") keeps what the kernel made and the backward runs
    # no second ssm_scan_fwd; outside a checkpoint region the identity
    y = checkpoint_name(y.astype(out_dtype), "ssm_scan")
    h = checkpoint_name(h, "ssm_states")
    return y, (u, delta, A, B, C, h)


def _scan_bwd(out_dtype, res, dy):
    u, delta, A, B, C, h = res
    du, dd, dA, dBt, dCt = _scan_backward(
        u, delta, A.T, _transposed(B), _transposed(C), h,
        dy.astype(jnp.float32), flash_attention._auto_interpret())
    return (du.astype(u.dtype), dd.astype(delta.dtype), dA.astype(A.dtype),
            _transposed(dBt).astype(B.dtype), _transposed(dCt).astype(C.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


def scan_reference(u, delta, A, B, C):
    """The same scan as a ``lax.scan`` over the positions, differentiable
    by jax: the CPU's path inside a ``shard_map`` (module docstring)."""
    def step(s, t):
        u_t, d_t, b_t, c_t = t
        s = jnp.exp(d_t[..., None] * A) * s + (d_t * u_t)[..., None] * \
            b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], -1)

    move = lambda t: jnp.moveaxis(t.astype(jnp.float32), 1, 0)
    # made from the inputs, so that it varies over the axes they vary over
    start = jnp.zeros_like(u[:, 0, :, None] * A)
    _, y = lax.scan(step, start, (move(u), move(delta), move(B), move(C)))
    return jnp.moveaxis(y, 0, 1)


def selective_scan(u, delta, A, B, C, out_dtype=jnp.float32):
    """``y`` [b, T, ch] of the scan in the module docstring, computed in
    float32 and returned in ``out_dtype``: ``u``, ``delta`` [b, T, ch],
    ``A`` [ch, N], ``B``, ``C`` [b, T, N]; T a multiple of 8."""
    args = [t.astype(jnp.float32) for t in (u, delta, A, B, C)]
    if flash_attention._use_jnp_fallback(u):
        return scan_reference(*args).astype(out_dtype)
    return _scan(*args, out_dtype)
