"""What `remat="full"` keeps (models/gpt.py `layer_stack`): each block's
input, what the flash kernel made (its output and `lse`), where the
backward reads it `wm`'s output, and a conv layer's two products (LFM2's
`W_in` and `W_out`); everything else is made again. Judged by the residuals
jax would save (count, shape and dtype: a named value may print as the
output of another primitive), by the gradients, which no `remat` mode may
change, and, for the conv layer, by the products the backward runs. Tiny
widths on the CPU, the kernels interpreted; a block with output norms
(Ouro's) beside a pre-norm one (Mistral's), and a hybrid stack of conv and
attention layers."""
import collections
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from kungfu_tpu.models import gpt as G
from kungfu_tpu.models import looped
# LFM2's hybrid stack: a dense conv layer, then attention and two conv
# layers, each routed by sigmoid scores
from tests.test_lfm2_layers import CFG as HYBRID

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


CONV_LAYERS = HYBRID.operator.count("conv")
# the policy before a conv layer's products were named in it
WITHOUT_CONV_NAMES = jax.checkpoint_policies.save_only_these_names(
    "ffn_proj", "flash_out", "flash_lse")


def hybrid_loss(cfg, remat):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, 64), 0, 96)

    def loss(p):
        feats = G.forward_features(p, tokens, cfg, attn="dense", remat=remat)
        return (feats.astype(jnp.float32) @ jnp.linspace(-1., 2., 64)).mean()
    return loss


def residuals(loss, params):
    return collections.Counter((a.shape, a.dtype)
                               for a, _ in saved_residuals(loss, params))


def dot_generals(jaxpr) -> int:
    """The products in ``jaxpr`` and in every jaxpr inside it."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "dot_general"
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    n += dot_generals(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    n += dot_generals(sub)
    return n


@pytest.mark.parametrize("remat", [True, "full"])
def test_a_conv_layer_keeps_its_two_products(remat, monkeypatch):
    """Against the policy without the names, each conv layer adds one
    ``[B, T, 3D]`` (``W_in``'s) and one ``[B, T, D]`` (``W_out``'s) to what
    the step keeps, and nothing else moves."""
    cfg = dataclasses.replace(HYBRID, dtype=jnp.bfloat16)
    params = G.init_params(jax.random.PRNGKey(0), cfg)
    loss = hybrid_loss(cfg, remat)
    named = residuals(loss, params)
    monkeypatch.setattr(G, "_FULL_REMAT_KEEPS", WITHOUT_CONV_NAMES)
    unnamed = residuals(loss, params)
    D = cfg.d_model
    assert not unnamed[((B, 64, 3 * D), BF16)]
    assert unnamed - named == {}, unnamed - named
    assert named - unnamed == {((B, 64, 3 * D), BF16): CONV_LAYERS,
                               ((B, 64, D), BF16): CONV_LAYERS}


def test_a_conv_layers_backward_runs_neither_product_again(monkeypatch):
    """Two products fewer a conv layer in the gradient: ``W_in`` and
    ``W_out`` are not made again in the backward."""
    cfg = dataclasses.replace(HYBRID, dtype=jnp.bfloat16)
    params = G.init_params(jax.random.PRNGKey(0), cfg)
    # a new function each time: make_jaxpr keeps the trace of one it saw
    count = lambda: dot_generals(jax.make_jaxpr(
        jax.grad(hybrid_loss(cfg, "full")))(params).jaxpr)
    named = count()
    monkeypatch.setattr(G, "_FULL_REMAT_KEEPS", WITHOUT_CONV_NAMES)
    unnamed = count()
    assert unnamed - named == 2 * CONV_LAYERS, (unnamed, named)


def test_the_hybrid_stacks_gradient_is_the_one_without_remat():
    params = G.init_params(jax.random.PRNGKey(0), HYBRID)
    full = jax.jit(jax.grad(hybrid_loss(HYBRID, "full")))(params)
    none = jax.jit(jax.grad(hybrid_loss(HYBRID, "")))(params)
    assert float(jnp.abs(full["layers"][0]["w_in"]).max()) > 0
    assert_same_grads(full, none)
