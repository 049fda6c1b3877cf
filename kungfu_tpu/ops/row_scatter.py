"""Rows added into a float32 carry that stays in HBM, as one Pallas call.

``scatter_add_rows(carry, idx, n_live, upd)`` adds ``upd[r]`` into the
carry's row ``idx[r]`` for each ``r < n_live``: the expert layer's return of
a block's output into its tokens (``parallel/moe.py``), in both passes.
XLA's scatter does that a row at a time, each row waiting on memory; here
every live row is a DMA of its own, many in flight, so the call is bound by
HBM's bandwidth and not by its latency, and the rows past ``n_live`` are
never touched.

A DMA moves whole (8, 128) tiles, and a row of an ``[n, D]`` float32 array
is one sublane of each of its tiles.  So the carry holds each row as tiles
of its own, ``[n, R, 128]`` (:func:`carry_shape`; D = 2048 is 16 such
lane-rows, two tiles), and :func:`rows_of` turns it back into ``[n, D]``
after the caller's loop.  ``upd`` comes as ``[block, D]``; the kernel
lays each row out as the carry's while it adds.

The carry is aliased input to output and updated in place.  The live rows
go in chunks through three VMEM slots: while chunk c is added, chunk c + 1's
rows (and its rows of ``upd``, one DMA a chunk) come in and chunk c - 1's go
back.  The caller's contract, on which the order of the DMAs rests: the live
rows are a prefix of the block and their rows of the carry differ, so no two
DMAs of a call touch one row.

On CPU (tests, CI) the call runs interpreted, by
``ops.flash_attention._auto_interpret``: the same additions, one a live row,
so its result is ``.at[idx[:n_live]].add(upd[:n_live])`` bit for bit.
Inside a ``shard_map`` that checks varying axes the interpreter cannot run
(its VMEM varies over no mesh axis), so there, on the CPU alone, the call is
that ``.at[].add``, as the flash kernels are their jnp twin there.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention

_LANES, _SUBLANES = 128, 8
_CHUNK = 64    # rows a chunk: a slot is 64 x D f32 (512 KB at D = 2048)
_SLOTS = 3     # chunks in VMEM at once: coming in, being added, going back
# two [slots, chunk, R, 128] f32 buffers are 3 MB at D = 2048 and 3.5 MB at
# 2560; the limit is set, not left to the 16 MiB scoped default, which has
# refused on the chip a flash call that the off-chip compile took
_VMEM_LIMIT = 32 * 1024 * 1024
_SLAB = 4096   # rows turned back at a time by rows_of


def carry_shape(n: int, D: int) -> tuple:
    """The shape of a carry of ``n`` rows of ``D`` that
    :func:`scatter_add_rows` adds into: ``(n, R, 128)``, each row as whole
    (8, 128) tiles, R rounded up to whole tiles (2560 = 20 lane-rows in
    24).  A width that is not whole lanes (tests on the CPU) is one
    lane-row, ``(n, 1, D)``."""
    if D % _LANES:
        return (n, 1, D)
    return (n, -(-D // (_LANES * _SUBLANES)) * _SUBLANES, _LANES)


def rows_of(carry, D: int, dtype):
    """A carry of :func:`carry_shape` as ``[n, D]`` in ``dtype``, turned
    back a slab of rows at a time: in one piece XLA casts the whole carry
    first and keeps the cast beside its relayout, a second ``[n, D]`` of
    temporaries (268 MB more in a 32,768-token step)."""
    n, _, L = carry.shape
    slab = math.gcd(n, _SLAB)
    return jnp.concatenate([
        carry[at:at + slab, :D // L].reshape(slab, D).astype(dtype)
        for at in range(0, n, slab)])


def _kernel(idx_ref, n_ref, carry_ref, upd_ref, out_ref, rows, upd, sem_in,
            sem_upd, sem_out, *, chunk: int):
    """``carry_ref`` and ``out_ref`` are one buffer in HBM (aliased); the
    rows are read from and written to ``out_ref``."""
    del carry_ref
    n = n_ref[0]
    n_chunks = (n + chunk - 1) // chunk
    R, L = upd.shape[2] // rows.shape[3], rows.shape[3]

    def each_live_row(c, f):
        lax.fori_loop(0, jnp.minimum(n - c * chunk, chunk),
                      lambda r, _: f(r, idx_ref[c * chunk + r]), None)

    def row(slot, r, tok, sem, out: bool):
        here, there = rows.at[slot, pl.ds(r, 1)], out_ref.at[pl.ds(tok, 1)]
        return pltpu.make_async_copy(*((here, there) if out else
                                       (there, here)), sem.at[slot])

    def wait_rows(c, sem):
        """A full chunk's rows are waited for at once (the semaphore counts
        bytes), the last chunk's row by row."""
        slot, full = c % _SLOTS, n - c * chunk >= chunk

        @pl.when(full)
        def _():
            pltpu.make_async_copy(rows.at[slot], rows.at[slot],
                                  sem.at[slot]).wait()

        @pl.when(jnp.logical_not(full))
        def _():
            each_live_row(c, lambda r, tok: row(slot, r, tok, sem,
                                                False).wait())

    def upd_in(c):
        slot = c % _SLOTS
        return pltpu.make_async_copy(upd_ref.at[pl.ds(c * chunk, chunk)],
                                     upd.at[slot], sem_upd.at[slot])

    def start_in(c):
        upd_in(c).start()
        each_live_row(c, lambda r, tok: row(c % _SLOTS, r, tok, sem_in,
                                            False).start())

    @pl.when(n_chunks > 0)
    def _():
        start_in(0)

    def visit(c, _):
        slot = c % _SLOTS

        # the slot chunk c + 1 comes into is chunk c - 2's: its rows home
        @pl.when(c >= _SLOTS - 1)
        def _():
            wait_rows(c - (_SLOTS - 1), sem_out)

        @pl.when(c + 1 < n_chunks)
        def _():
            start_in(c + 1)

        upd_in(c).wait()
        wait_rows(c, sem_in)

        def add(r, _):
            rows[slot, r, :R] = rows[slot, r, :R] + upd[
                slot, pl.ds(r, 1), :].reshape(R, L)
        lax.fori_loop(0, chunk, add, None)
        each_live_row(c, lambda r, tok: row(slot, r, tok, sem_out,
                                            True).start())

    lax.fori_loop(0, n_chunks, visit, None)
    for back in range(_SLOTS - 1, 0, -1):
        @pl.when(n_chunks >= back)
        def _():
            wait_rows(n_chunks - back, sem_out)


def scatter_add_rows(carry, idx, n_live, upd):
    """``carry`` (:func:`carry_shape` of ``[n, D]``, float32) with
    ``upd[r]`` added into row ``idx[r]`` for each ``r < n_live``: ``idx``
    [block] int32, ``n_live`` an int32 scalar, ``upd`` [block, D] float32.
    The caller promises that ``idx[:n_live]`` holds no row twice; rows past
    ``n_live`` are not read.  ``carry`` is updated in place where the
    caller's buffer can be (a loop's carry); the call is named
    ``moe_scatter_add``."""
    n, D, block = carry.shape[0], upd.shape[1], idx.shape[0]
    if carry.shape != carry_shape(n, D) or upd.shape[0] != block:
        raise ValueError(f"moe_scatter_add: a carry {carry.shape} and upd "
                         f"{upd.shape} for {block} rows, where the carry of "
                         f"[{n}, {D}] is {carry_shape(n, D)}")
    if flash_attention._use_jnp_fallback(carry):
        # the padding past n_live dropped, as the kernel never reads it
        R, L = D // carry.shape[2], carry.shape[2]
        at = jnp.where(jnp.arange(block) < n_live, idx, n)
        return carry.at[at, :R].add(upd.reshape(block, R, L), mode="drop")
    interpret = flash_attention._auto_interpret()
    if not interpret and D % _LANES:
        raise ValueError(f"moe_scatter_add moves rows of whole 128-lane "
                         f"tiles on the chip; D = {D} is not a multiple "
                         f"of 128")
    chunk = math.gcd(block, _CHUNK)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((_SLOTS, chunk) + carry.shape[1:],
                                   jnp.float32),
                        pltpu.VMEM((_SLOTS, chunk, D), jnp.float32),
                        pltpu.SemaphoreType.DMA((_SLOTS,)),
                        pltpu.SemaphoreType.DMA((_SLOTS,)),
                        pltpu.SemaphoreType.DMA((_SLOTS,))])
    return pl.pallas_call(
        lambda *refs: _kernel(*refs, chunk=chunk),
        grid_spec=grid_spec,
        out_shape=flash_attention._sds(carry.shape, carry.dtype, carry),
        # operands: idx, n_live, carry, upd; the carry is the output
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_scatter_add",
    )(idx.astype(jnp.int32), jnp.reshape(n_live, (1,)).astype(jnp.int32),
      carry, upd)
