"""What `remat="full"` keeps (models/gpt.py `layer_stack`): each block's
input, what the flash kernel made (its output and `lse`) and, where the
backward reads it, `wm`'s output; everything else is made again. Judged by
the residuals jax would save (count, shape and dtype: a named value may
print as the output of another primitive) and by the gradients, which no
`remat` mode may change. Tiny widths on the CPU, the kernels interpreted;
a block with output norms (Ouro's) beside a pre-norm one (Mistral's)."""
import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from kungfu_tpu.models import gpt as G
from kungfu_tpu.models import looped

B, T, D, H, DH, F, L, R = 2, 128, 32, 4, 8, 48, 2, 2
BF16, F32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
both_blocks = pytest.mark.parametrize("out_norms", [False, True])


def config(out_norms, dtype=jnp.float32, **kw):
    return G.GPTConfig(vocab_size=64, d_model=D, n_heads=H, n_layers=L,
                       d_ff=F, max_seq=T, n_kv_heads=2, rope=True,
                       mlp="swiglu", dtype=dtype, out_norms=out_norms, **kw)


def batch():
    rng = np.random.RandomState(0)
    return (jnp.asarray(rng.randint(0, 64, (B, T)), jnp.int32),
            jnp.asarray(rng.randint(0, 64, (B, T)), jnp.int32))


def plain_loss(cfg, attn, remat):
    tokens, _ = batch()

    def loss(p):    # linear in the features: it keeps no activation
        feats = G.forward_features(p, tokens, cfg, attn=attn, remat=remat)
        return (feats.astype(jnp.float32) @ jnp.linspace(-1., 2., D)).mean()
    return loss


@functools.lru_cache(maxsize=None)
def plain_grads(out_norms, remat):
    cfg = config(out_norms)
    params = G.init_params(jax.random.PRNGKey(0), cfg)
    return jax.jit(jax.grad(plain_loss(cfg, "flash", remat)))(params)


def looped_loss(cfg, attn, remat):
    tokens, targets = batch()
    return lambda p: looped.loss_fn(p, tokens, targets, cfg, beta=0.1,
                                    ce_chunk=32, attn=attn, remat=remat)


def kept(loss, params, lead=()):
    """{(shape, dtype): count} of the activations among the residuals that
    `loss`'s backward would find saved: floating, of rank three or more
    behind `lead` (the scan's stacking), the batch first."""
    n = len(lead)
    return collections.Counter(
        (a.shape[n:], a.dtype) for a, _ in saved_residuals(loss, params)
        if a.shape[:n + 1] == lead + (B,) and a.ndim - n >= 3
        and jnp.issubdtype(a.dtype, jnp.floating))


def assert_same_grads(got, want):
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=1e-6)


@both_blocks
@pytest.mark.parametrize("remat", ["", "ffn", "attn"])
def test_no_remat_mode_changes_the_gradient(remat, out_norms):
    """`"full"` against no remat, and against the two modes whose regions
    have no policy: there a name is the identity."""
    full = plain_grads(out_norms, "full")
    assert float(jnp.abs(full["layers"][0]["wm"]).max()) > 0
    assert_same_grads(full, plain_grads(out_norms, remat))


@both_blocks
def test_full_remat_keeps_what_the_kernel_and_wm_made(out_norms):
    cfg = config(out_norms, jnp.bfloat16)
    params = G.init_params(jax.random.PRNGKey(0), cfg)
    full = kept(plain_loss(cfg, "flash", "full"), params)
    assert full == {
        # each block's input and the final norm's; wm's output where an
        # output norm reads it in the backward
        ((B, T, D), BF16): L + 1 + (L if out_norms else 0),
        ((B, T, H, DH), BF16): L,       # flash_out
        ((B, H, T), F32): L,            # flash_lse, compact
    }, full
    # and without remat the FFN's wide activations are there to be found
    none = kept(plain_loss(cfg, "flash", ""), params)
    assert none[((B, T, F), BF16)] and none[((B, T, D), BF16)] > 3 * L


@both_blocks
def test_dense_attention_under_full_remat_keeps_nothing_new(out_norms):
    """No kernel, no name: the block's input alone (and `ffn_proj`)."""
    cfg = config(out_norms, jnp.bfloat16)
    params = G.init_params(jax.random.PRNGKey(0), cfg)
    full = kept(plain_loss(cfg, "dense", "full"), params)
    assert full == {
        ((B, T, D), BF16): L + 1 + (L if out_norms else 0)}, full


@both_blocks
def test_a_looped_model_keeps_the_same_a_visit(out_norms):
    """Two rounds under one set of weights: the scan stacks each round's
    residuals, so every kept value has the rounds in front."""
    cfg = config(out_norms, jnp.bfloat16, n_rounds=R)
    params = looped.init_params(jax.random.PRNGKey(0), cfg)
    full = kept(looped_loss(cfg, "flash", "full"), params, lead=(R,))
    assert full == {
        # a round's block inputs, its final norm's input and the normed
        # state the heads read; wm's output under an output norm
        ((B, T, D), BF16): L + 2 + (L if out_norms else 0),
        ((B, T, H, DH), BF16): L,
        ((B, H, T), F32): L,
    }, full

    cfg = config(out_norms, n_rounds=R)
    full = jax.jit(jax.grad(looped_loss(cfg, "flash", "full")))(params)
    none = jax.jit(jax.grad(looped_loss(cfg, "flash", "")))(params)
    assert_same_grads(full, none)
