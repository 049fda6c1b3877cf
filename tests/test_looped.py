"""The looped decoder (models/looped.py) tied to the plain one: one round is
`forward_features` + the chunked head, four rounds' gradient is the sum of
the rounds' gradients with the weights untied, the exit distribution is a
distribution; and the configuration's eps, RoPE base and output norms
reach the decode path as they reach training, while a looped model is
refused there by name."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kungfu_tpu.models import gpt as G
from kungfu_tpu.models import looped
from kungfu_tpu.ops.chunked_ce import chunked_cross_entropy

F32 = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=48,
           max_seq=16, dtype=jnp.float32, rope=True, mlp="swiglu")
tmap = jax.tree_util.tree_map


def batch(seed=0, B=2, T=16, V=64):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randint(0, V, (B, T)), jnp.int32),
            jnp.asarray(rng.randint(0, V, (B, T)), jnp.int32))


def test_one_round_is_the_plain_decoder():
    """R = 1, the output norms off, the gate's weights left out: features
    and loss are forward_features + chunked_cross_entropy(...).mean()."""
    cfg = G.GPTConfig(**F32)
    params = G.init_params(jax.random.PRNGKey(0), cfg)      # no exit_gate
    tokens, targets = batch()
    feats = jax.jit(lambda p: G.forward_features(p, tokens, cfg,
                                                 attn="dense"))(params)
    hs = jax.jit(lambda p: looped.forward_rounds(p, tokens, cfg,
                                                 attn="dense"))(params)
    assert hs.shape == (1,) + feats.shape
    np.testing.assert_allclose(hs[0], feats, rtol=1e-5, atol=1e-5)
    want = chunked_cross_entropy(feats, params["lm_head"], targets,
                                 16).mean()
    got = jax.jit(lambda p: looped.loss_fn(p, tokens, targets, cfg, beta=0.1,
                                           ce_chunk=16, attn="dense"))(params)
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("remat", ["", "full"])
def test_a_shared_weights_gradient_is_the_sum_of_its_rounds(remat):
    """R = 4 under one set of weights against the same four rounds written
    out with a copy of the layers for each: the tied gradient of a layer's
    weight is the sum of the four untied ones."""
    R = 4
    cfg = G.GPTConfig(**F32, out_norms=True, n_rounds=R, norm_eps=1e-6,
                      rope_theta=1e6)
    params = looped.init_params(jax.random.PRNGKey(1), cfg)
    tokens, targets = batch(1)
    loss, tied = jax.jit(jax.value_and_grad(lambda p: looped.loss_fn(
        p, tokens, targets, cfg, beta=0.1, ce_chunk=32, attn="dense",
        remat=remat)))(params)

    one = dataclasses.replace(cfg, n_rounds=1)

    def untied_loss(layer_copies, params):
        x, _ = G.layer_stack(params, tokens, one, attn="dense")
        hs = []
        for layers in layer_copies:
            _, run = G.layer_stack(dict(params, layers=layers), tokens, one,
                                   attn="dense")
            x = G.rms_norm(run(x), params["lnf"], cfg.norm_eps)
            hs.append(x)
        hs = jnp.stack(hs)
        gate = params["exit_gate"]
        logp = looped.exit_log_probs(hs[:-1] @ gate["w"] + gate["b"])
        ce = jnp.stack([chunked_cross_entropy(h, params["lm_head"], targets,
                                              32) for h in hs])
        return jnp.sum(jnp.exp(logp) * (ce + 0.1 * logp), 0).mean()

    copies = [params["layers"]] * R
    loss_u, per_round = jax.jit(jax.value_and_grad(untied_loss))(copies,
                                                                 params)
    np.testing.assert_allclose(loss, loss_u, rtol=1e-6)
    summed = tmap(lambda *g: sum(g), *per_round)
    for got, want in zip(jax.tree_util.tree_leaves(tied["layers"]),
                         jax.tree_util.tree_leaves(summed)):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-6)
    # and no round's share is nought: every round reaches the loss
    for g in per_round:
        assert float(jnp.abs(g[0]["wq"]).max()) > 0


def test_the_exit_distribution_is_one():
    z = jnp.asarray(np.random.RandomState(2).randn(3, 5, 7) * 3, jnp.float32)
    logp = looped.exit_log_probs(z)
    assert logp.shape == (4, 5, 7)
    np.testing.assert_allclose(jnp.exp(logp).sum(0), 1.0, rtol=1e-6)
    lam = jax.nn.sigmoid(z)
    np.testing.assert_allclose(jnp.exp(logp[1]), lam[1] * (1 - lam[0]),
                               rtol=1e-4)
    np.testing.assert_allclose(jnp.exp(logp[3]), jnp.prod(1 - lam, 0),
                               rtol=1e-4)
    # one round: nothing to gate, the whole mass on it
    assert looped.exit_log_probs(jnp.zeros((0, 5))).tolist() == [[0.0] * 5]


def test_the_looped_tree_is_the_plain_one_and_a_gate():
    cfg = G.GPTConfig(**F32, out_norms=True, n_rounds=4)
    params = looped.init_params(jax.random.PRNGKey(0), cfg)
    assert params["exit_gate"]["w"].shape == (32,)
    assert params["exit_gate"]["b"].shape == ()
    assert {"ln1_out", "ln2_out"} <= set(params["layers"][0])
    specs = G.param_specs(cfg)
    plain = {k: v for k, v in params.items() if k != "exit_gate"}
    assert jax.tree_util.tree_structure(plain) == (
        jax.tree_util.tree_structure(
            specs, is_leaf=lambda x: isinstance(
                x, jax.sharding.PartitionSpec)))


def _decode_all(params, cfg, tokens):
    """Every position's features through the incremental decode path."""
    cache = G.init_kv_cache(cfg, tokens.shape[0], tokens.shape[1])
    out = []
    for t in range(tokens.shape[1]):
        x, cache = G._decode_hidden(params, cfg, cache, jnp.int32(t),
                                    tokens[:, t])
        out.append(x[:, 0])
    return jnp.stack(out, 1)


@pytest.mark.parametrize("change", [dict(norm_eps=0.3),
                                    dict(rope_theta=20.0),
                                    dict(out_norms=True)])
def test_eps_base_and_output_norms_reach_decode_as_they_reach_training(
        change):
    base = G.GPTConfig(**F32)
    cfg = dataclasses.replace(base, **change)
    params = G.init_params(jax.random.PRNGKey(3), cfg)
    if cfg.out_norms:       # norms off one, so that they are seen to act
        params["layers"] = [dict(l, ln1_out=l["ln1_out"] * 0.5,
                                 ln2_out=l["ln2_out"] * 1.5)
                            for l in params["layers"]]
    tokens, _ = batch(3)
    train = jax.jit(lambda p: G.forward_features(p, tokens, cfg,
                                                 attn="dense"))(params)
    np.testing.assert_allclose(_decode_all(params, cfg, tokens), train,
                               rtol=2e-4, atol=2e-5)
    # the setting is not a no-op: the default configuration differs
    default = G.forward_features(params, tokens, base, attn="dense")
    assert float(jnp.abs(train - default).max()) > 1e-3


def test_defaults_are_the_constants_they_replace():
    cfg = G.GPTConfig()
    assert (cfg.norm_eps, cfg.rope_theta, cfg.out_norms, cfg.n_rounds) == (
        1e-5, 10000.0, False, 1)
    with pytest.raises(ValueError, match="n_rounds"):
        G.GPTConfig(n_rounds=0)


def test_a_looped_model_is_refused_where_nothing_runs_its_rounds():
    from kungfu_tpu.serving import DecodeEngine
    cfg = G.GPTConfig(**F32, n_rounds=4)
    params = looped.init_params(jax.random.PRNGKey(0), cfg)
    tokens, _ = batch()
    with pytest.raises(ValueError, match="n_rounds=4"):
        G.forward_features(params, tokens, cfg)
    with pytest.raises(ValueError, match="n_rounds=4"):
        G.decode_step(params, cfg, G.init_kv_cache(cfg, 2), jnp.int32(0),
                      tokens[:, 0])
    with pytest.raises(ValueError, match="n_rounds=4"):
        DecodeEngine(params, cfg)
    # nor do the pipelined and the expert-layer trainers, which walk the
    # layers once themselves
    import optax
    from kungfu_tpu.comm.mesh import flat_mesh
    from kungfu_tpu.parallel import moe_gpt, pipeline
    with pytest.raises(ValueError, match="n_rounds=4"):
        pipeline.make_gpt_pp_train_step(
            cfg, optax.sgd(0.1), flat_mesh(jax.devices()[:1]), n_micro=1)
    with pytest.raises(ValueError, match="n_rounds=4"):
        moe_gpt.forward_local(params, tokens, moe_gpt.MoEGPTConfig(gpt=cfg))
