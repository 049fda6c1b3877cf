"""Ring-flash attention: Pallas per-chunk kernels + lse merge vs oracles.

Covers the differentiable-lse extension of the flash kernel (its lse
cotangent folds into the backward row term) and the full ring schedule's
forward/gradient parity against dense attention over the concatenated
sequence.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kungfu_tpu.ops.flash_attention import (flash_attention_with_lse)
from kungfu_tpu.parallel import (reference_attention, ring_attention,
                                 ring_flash_attention)


def _qkv(B=2, T=32, H=2, D=16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    return mk(), mk(), mk()


def _dense_lse(q, k, v, causal):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        Tq, Tk = s.shape[2], s.shape[3]
        mask = jnp.arange(Tq)[:, None] >= jnp.arange(Tk)[None, :]
        s = jnp.where(mask[None, None], s, -1e30)
    return jax.scipy.special.logsumexp(s, axis=-1)  # [B, H, Tq]


@pytest.mark.parametrize("causal", [False, True])
def test_lse_output_matches_dense(causal):
    q, k, v = _qkv()
    _, lse = flash_attention_with_lse(q, k, v, causal, 16, 16)
    want = _dense_lse(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_lse_gradient_matches_dense(causal):
    """The lse cotangent path: a loss that depends on BOTH outputs."""
    q, k, v = _qkv(seed=1)

    def loss_flash(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal, 16, 16)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    def loss_dense(q, k, v):
        o = reference_attention(q, k, v, causal=causal)
        lse = _dense_lse(q, k, v, causal)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def _ring_specs():
    return P(None, "sp", None, None)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("n", [2, 4])
def test_ring_flash_matches_dense(devices, causal, n):
    B, T, H, D = 2, 32, 2, 16
    q, k, v = _qkv(B=B, T=T, H=H, D=D, seed=2)
    mesh = Mesh(np.array(devices[:n]), ("sp",))
    fn = jax.jit(jax.shard_map(
        functools.partial(ring_flash_attention, axis_name="sp",
                          causal=causal, block_q=8, block_k=8),
        mesh=mesh, in_specs=(_ring_specs(),) * 3, out_specs=_ring_specs()))
    got = fn(q, k, v)
    want = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_ring_flash_gradients_match_ring(devices):
    """Grads through the whole ring (kernel vjp + lse merge + ppermute
    transpose) against the dense-block ring implementation."""
    B, T, H, D = 2, 16, 2, 8
    q, k, v = _qkv(B=B, T=T, H=H, D=D, seed=3)
    mesh = Mesh(np.array(devices[:4]), ("sp",))

    def make_loss(attn_fn):
        sm = jax.shard_map(
            functools.partial(attn_fn, axis_name="sp", causal=True),
            mesh=mesh, in_specs=(_ring_specs(),) * 3,
            out_specs=_ring_specs())
        return lambda q, k, v: jnp.sum(sm(q, k, v) ** 2)

    rf = functools.partial(ring_flash_attention, block_q=4, block_k=4)
    # each side one compiled program: op by op they take several times as
    # long
    gf = jax.jit(jax.grad(make_loss(rf), argnums=(0, 1, 2)))(q, k, v)
    gd = jax.jit(jax.grad(make_loss(ring_attention), argnums=(0, 1, 2)))(
        q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_gqa_compact_gradients_match_dense(devices, causal):
    """kv_groups>1 through the WHOLE ring: forward AND gradients with
    compact KV (the production GQA sequence-parallel train path — the
    _fal_bwd combination of a live lse cotangent with the compact-KV
    group-sum adjoint is exercised only here)."""
    B, T, H, D, g = 2, 32, 4, 8, 2
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    kc = jnp.asarray(rng.randn(B, T, H // g, D).astype(np.float32))
    vc = jnp.asarray(rng.randn(B, T, H // g, D).astype(np.float32))
    mesh = Mesh(np.array(devices[:4]), ("sp",))

    ring = jax.shard_map(
        functools.partial(ring_flash_attention, axis_name="sp",
                          causal=causal, block_q=8, block_k=8,
                          kv_groups=g),
        mesh=mesh, in_specs=(_ring_specs(),) * 3,
        out_specs=_ring_specs())

    def loss_ring(q, kc, vc):
        return jnp.sum(ring(q, kc, vc).astype(jnp.float32) ** 2)

    expand = lambda t: jnp.repeat(t, g, axis=2)

    def loss_dense(q, kc, vc):
        o = reference_attention(q, expand(kc), expand(vc), causal=causal)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    got = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, kc, vc)
    want = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))(q, kc, vc)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)
