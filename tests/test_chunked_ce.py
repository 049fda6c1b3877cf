"""Chunked-vocab cross-entropy vs the dense oracle (value and gradients)."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from kungfu_tpu.ops.chunked_ce import chunked_cross_entropy


def dense_ce(x, w, targets):
    logits = jnp.einsum("btd,dv->btv", x.astype(jnp.float32), w)
    return optax.softmax_cross_entropy_with_integer_labels(logits, targets)


def make_case(B=2, T=8, D=16, V=64, seed=0, dtype=np.float32):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(B, T, D).astype(dtype))
    w = jnp.asarray((rng.randn(D, V) * 0.3).astype(dtype))
    y = jnp.asarray(rng.randint(0, V, (B, T)), jnp.int32)
    return x, w, y


@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_loss_matches_dense(chunk):
    x, w, y = make_case()
    got = chunked_cross_entropy(x, w, y, chunk)
    want = dense_ce(x, w, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_grads_match_dense():
    x, w, y = make_case(seed=1)

    def loss_c(x, w):
        return chunked_cross_entropy(x, w, y, 16).mean()

    def loss_d(x, w):
        return dense_ce(x, w, y).mean()

    gx_c, gw_c = jax.grad(loss_c, argnums=(0, 1))(x, w)
    gx_d, gw_d = jax.grad(loss_d, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx_c), np.asarray(gx_d),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gw_c), np.asarray(gw_d),
                               rtol=1e-4, atol=1e-6)


def test_grads_match_with_repeated_targets():
    """Duplicate target ids must all be taken off their one column of dW
    (the comparison hits once per token, not once per column)."""
    x, w, _ = make_case(seed=2)
    y = jnp.zeros((2, 8), jnp.int32)  # every token targets vocab id 0

    gw_c = jax.grad(lambda w: chunked_cross_entropy(x, w, y, 16).mean())(w)
    gw_d = jax.grad(lambda w: dense_ce(x, w, y).mean())(w)
    np.testing.assert_allclose(np.asarray(gw_c), np.asarray(gw_d),
                               rtol=1e-4, atol=1e-6)


def test_bf16_inputs_close_to_f32():
    x, w, y = make_case(seed=3)
    xb, wb = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    got = chunked_cross_entropy(xb, wb, y, 32)
    want = dense_ce(x, w, y)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-2, atol=5e-2)
    gx = jax.grad(lambda a: chunked_cross_entropy(a, wb, y, 32).mean())(xb)
    assert gx.dtype == jnp.bfloat16


def test_indivisible_chunk_rejected():
    x, w, y = make_case()
    with pytest.raises(ValueError, match="not divisible"):
        chunked_cross_entropy(x, w, y, 48)


def test_jit_and_scan_compatible():
    """Must compose with jit and grad under jit (scan inside custom_vjp)."""
    x, w, y = make_case(seed=4)
    f = jax.jit(lambda x, w: chunked_cross_entropy(x, w, y, 32).mean())
    g = jax.jit(jax.grad(f, argnums=1))
    assert np.isfinite(float(f(x, w)))
    assert np.all(np.isfinite(np.asarray(g(x, w))))


def _primitives_and_vars(jaxpr):
    """Every primitive's name and every variable's (dtype, shape) in a
    jaxpr and the jaxprs its equations hold (scan, pjit, custom_vjp)."""
    names, avals = set(), set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for v in eqn.outvars:
            avals.add((str(v.aval.dtype), tuple(v.aval.shape)))
        for val in eqn.params.values():
            for sub in val if isinstance(val, (list, tuple)) else (val,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n, a = _primitives_and_vars(sub)
                    names |= n
                    avals |= a
    return names, avals


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_no_gather_no_scatter_no_f32_head_sized_buffer(dtype):
    """The target term is a comparison inside the chunk loops, so value
    and gradient hold no gather and no scatter; and with a bf16 head each
    chunk's dW is cast where it is made: nothing f32 of shape [D, V]."""
    x, w, y = make_case(seed=5)
    x, w = x.astype(dtype), w.astype(dtype)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda x, w: chunked_cross_entropy(x, w, y, 16).mean(),
        argnums=(0, 1)))(x, w)
    names, avals = _primitives_and_vars(jaxpr.jaxpr)
    assert "dot_general" in names            # the walk reached the loops
    assert not [n for n in names if "gather" in n or "scatter" in n]
    if dtype == "bfloat16":
        assert ("float32", w.shape) not in avals
        assert ("bfloat16", w.shape) in avals


def _edge_targets(V, chunk):
    edges = jnp.asarray([0, chunk - 1, chunk, V - 1], jnp.int32)
    return jnp.tile(edges, 4).reshape(2, 8)


def _one_chunk_targets(V, chunk):
    rng = np.random.RandomState(6)
    return jnp.asarray(rng.randint(chunk, 2 * chunk, (2, 8)), jnp.int32)


@pytest.mark.parametrize("targets", [_edge_targets, _one_chunk_targets])
def test_targets_on_chunk_edges_and_in_one_chunk(targets):
    x, w, _ = make_case(seed=6)
    y = targets(64, 16)
    np.testing.assert_allclose(
        np.asarray(chunked_cross_entropy(x, w, y, 16)),
        np.asarray(dense_ce(x, w, y)), rtol=1e-5, atol=1e-6)
    got = jax.grad(lambda x, w: chunked_cross_entropy(x, w, y, 16).mean(),
                   argnums=(0, 1))(x, w)
    want = jax.grad(lambda x, w: dense_ce(x, w, y).mean(),
                    argnums=(0, 1))(x, w)
    for g, d in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(d),
                                   rtol=1e-4, atol=1e-6)


def test_grads_match_under_a_per_token_cotangent():
    """A weighted sum hands the backward a cotangent that differs by
    token (and is zero or negative for some), not .mean()'s constant."""
    x, w, y = make_case(seed=7)
    weight = jnp.asarray(np.random.RandomState(7).randn(2, 8)
                         .astype(np.float32)).at[0, 0].set(0.0)
    got = jax.grad(lambda x, w: (chunked_cross_entropy(x, w, y, 16)
                                 * weight).sum(), argnums=(0, 1))(x, w)
    want = jax.grad(lambda x, w: (dense_ce(x, w, y) * weight).sum(),
                    argnums=(0, 1))(x, w)
    for g, d in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(d),
                                   rtol=1e-4, atol=1e-6)


def test_bf16_head_gradient_is_bf16_and_close_to_f32():
    x, w, y = make_case(seed=8)
    xb, wb = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    gw = jax.grad(lambda b: chunked_cross_entropy(xb, b, y, 32).mean())(wb)
    assert gw.dtype == jnp.bfloat16
    want = jax.grad(lambda w: dense_ce(x, w, y).mean())(w)
    np.testing.assert_allclose(np.asarray(gw, np.float32), np.asarray(want),
                               rtol=5e-2, atol=5e-2)
    # against the same rounded inputs only the op's own rounding is left
    same = jax.grad(lambda w: dense_ce(xb.astype(jnp.float32), w, y).mean()
                    )(wb.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(gw, np.float32), np.asarray(same),
                               rtol=2e-2, atol=2e-3)


def test_inside_a_shard_map_over_the_batch(devices):
    """How the trainers use it: the batch split over one mesh axis, every
    peer with its own copy of the head, value and gradients taken inside.
    The loops' carries inherit the varying axis from ``x``."""
    x, w, y = make_case(B=4, seed=9)
    mesh = Mesh(np.array(devices[:2]), ("b",))

    def local(x, w, y):
        loss, (gx, gw) = jax.value_and_grad(
            lambda x, w: chunked_cross_entropy(x, w[0], y, 16).sum() / y.size
            / 2, argnums=(0, 1))(x, w)
        return lax.psum(loss, "b"), gx, lax.psum(gw[0], "b")

    loss, gx, gw = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("b"), P("b"), P("b")),
        out_specs=(P(), P("b"), P())))(x, jnp.stack([w, w]), y)
    want, (gx_d, gw_d) = jax.value_and_grad(
        lambda x, w: dense_ce(x, w, y).mean(), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_d),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_d),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("bad", [-1, 64, 1000])
def test_out_of_range_target_has_no_target_term(bad):
    """Not checked, and no longer clamped: the token's loss is the bare
    logsumexp and its gradient the softmax's."""
    x, w, y = make_case(seed=10)
    y = y.at[1, 3].set(bad)
    lse = jax.nn.logsumexp(jnp.einsum("btd,dv->btv", x, w), axis=-1)
    want = dense_ce(x, w, jnp.clip(y, 0, 63)).at[1, 3].set(lse[1, 3])
    np.testing.assert_allclose(
        np.asarray(chunked_cross_entropy(x, w, y, 16)), np.asarray(want),
        rtol=1e-5, atol=1e-6)
    gx = jax.grad(lambda x: chunked_cross_entropy(x, w, y, 16)[1, 3])(x)
    gx_d = jax.grad(lambda x: jax.nn.logsumexp(x[1, 3] @ w))(x)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_d),
                               rtol=1e-4, atol=1e-6)
