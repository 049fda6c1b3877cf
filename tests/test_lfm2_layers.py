"""The hybrid stack of models/gpt.py and parallel/moe.py that the `lfm2`
family trains: the gated short convolution is causal, the sigmoid router's
bias moves the choice and never a weight and moves by the sign rule, a norm
stands on q and k, the four shares of the experts add up to the uncut
layer, the probe counts the held rows, and every path that walks layers of
one kind refuses the stack by name."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kungfu_tpu.models import gpt
from kungfu_tpu.parallel import moe

# a dense conv layer, then attention with qk-norm and two conv layers, each
# routed over 8 experts (2 a token, all held) by sigmoid scores read from
# the feed-forward's norm
CFG = gpt.GPTConfig(vocab_size=96, d_model=64, n_heads=4, n_kv_heads=2,
                    n_layers=4, d_ff=96, max_seq=64, dtype=jnp.float32,
                    rope=True, mlp="swiglu", n_experts=8,
                    experts_per_token=2, d_expert=32,
                    routed=(False, True, True, True), router="sigmoid",
                    router_reads="ln2", operator=("conv", "attn", "conv",
                                                  "conv"), qk_norm=True)


def _model(cfg=CFG, seed=0):
    params = gpt.init_params(jax.random.PRNGKey(seed), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, 64), 0, 96)
    bias = [0.1 * jax.random.normal(jax.random.PRNGKey(seed + 2 + i), (8,))
            for i in range(3)]
    return params, tokens, bias


@pytest.mark.parametrize("t", [0, 17, 63])
def test_the_short_convolution_is_causal(t):
    params, _, _ = _model()
    layer = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 64))
    moved = x.at[:, t].add(1.0)
    a, b = gpt._short_conv(layer, x, CFG), gpt._short_conv(layer, moved, CFG)
    # nothing before t moves, position t does
    np.testing.assert_array_equal(a[:, :t], b[:, :t])
    assert float(jnp.max(jnp.abs(a[:, t] - b[:, t]))) > 1e-3
    # and the three taps reach two positions back, no further
    if t + 3 < 64:
        np.testing.assert_allclose(a[:, t + 3:], b[:, t + 3:], rtol=0,
                                   atol=1e-5)


def test_the_short_convolution_is_the_three_tap_sum():
    params, _, _ = _model()
    layer = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 64, 64))
    h = np.asarray(gpt.rms_norm(x, layer["ln1"], CFG.norm_eps))[0]
    b, c, v = np.split(h @ np.asarray(layer["w_in"]), 3, -1)
    u, taps = b * v, np.asarray(layer["conv"])
    z = np.stack([sum(taps[j] * u[t - j] for j in range(3) if t - j >= 0)
                  for t in range(64)])
    np.testing.assert_allclose(gpt._short_conv(layer, x, CFG)[0],
                               (c * z) @ np.asarray(layer["w_out"]),
                               rtol=1e-4, atol=1e-4)


def test_the_whole_stack_is_causal():
    params, tokens, bias = _model()
    # one compiled program for both: op by op it takes twice as long
    run = jax.jit(lambda tok: gpt.forward_features(
        params, tok, CFG, attn="dense", router_bias=bias)[0])
    a, b = run(tokens), run(tokens.at[:, 40].set((tokens[:, 40] + 1) % 96))
    np.testing.assert_array_equal(a[:, :40], b[:, :40])
    assert float(jnp.max(jnp.abs(a[:, 40:] - b[:, 40:]))) > 1e-3


def test_the_bias_moves_the_choice_and_never_a_weight():
    h = jax.random.normal(jax.random.PRNGKey(5), (32, 16))
    router = jax.random.normal(jax.random.PRNGKey(6), (16, 8)) / 4
    ids, w = moe.route_topk(h, router, 2, score="sigmoid")
    s = jax.nn.sigmoid(h @ router)
    np.testing.assert_array_equal(ids, jax.lax.top_k(s, 2)[1])
    # a bias that makes expert 5 every token's first choice
    pushed, pw = moe.route_topk(h, router, 2, score="sigmoid",
                                bias=jnp.zeros(8).at[5].set(10.0))
    assert (np.asarray(pushed[:, 0]) == 5).all()
    assert not np.array_equal(pushed, ids)
    # the weights are the chosen scores, over their sum and 1e-6, whatever
    # the bias was
    for i, ww in ((ids, w), (pushed, pw)):
        chosen = jnp.take_along_axis(s, i, -1)
        np.testing.assert_allclose(
            ww, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-5)
    # the softmax router takes no bias
    with pytest.raises(ValueError, match="bias"):
        moe.route_topk(h, router, 2, bias=jnp.zeros(8))


def test_the_bias_moves_by_the_sign_rule():
    load = jnp.asarray([10, 2, 6, 6, 0, 12], jnp.int32)
    got = moe.update_expert_bias(jnp.full(6, 0.5), load, 1e-3)
    np.testing.assert_allclose(got, 0.5 + 1e-3 * np.array(
        [-1, 1, 0, 0, 1, -1]), rtol=0, atol=1e-7)
    ids = jnp.asarray([[0, 1], [1, 3], [1, 0]])
    np.testing.assert_array_equal(moe.held_assignments(ids, (0, 4)),
                                  [2, 3, 0, 1])


def test_a_norm_stands_on_q_and_k():
    params, _, _ = _model()
    layer = params["layers"][1]
    x = 5 * jax.random.normal(jax.random.PRNGKey(7), (1, 8, 64))
    q, k, _ = gpt._layer_qkv(layer, x, CFG, pos=jnp.arange(8), rope=False)
    for t in (q, k):
        np.testing.assert_allclose(jnp.sqrt(jnp.mean(t * t, -1)), 1,
                                   rtol=1e-3)
    q2, _, _ = gpt._layer_qkv(layer, x, dataclasses.replace(
        CFG, qk_norm=False), pos=jnp.arange(8), rope=False)
    assert not np.allclose(q, q2)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Guide section 4: four chips' parts of a routed conv layer, each from
    its own two of the eight experts, with what every chip computes alike
    (the convolution and the residual) counted once, sum to the plain
    reference's layer given every expert."""
    from perf.reference import gpt as plain, lfm2 as ref
    params, _, bias = _model()
    layer, b = params["layers"][2], bias[1]
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 64, 64))
    parts = []
    for a in (0, 2, 4, 6):
        cfg = dataclasses.replace(CFG, experts_held=(a, 2))
        mine = dict(layer, wi=layer["wi"][a:a + 2], wm=layer["wm"][a:a + 2])
        parts.append(gpt.apply_layer(mine, x, cfg, index=2, bias=b)[0])
    alike = x + gpt._short_conv(layer, x, CFG)
    s = dict(D=64, K=3, eps=CFG.norm_eps, k=2, first=0, G=8, E=8,
             norm_topk=True, scale=1, types=("conv",) * 4,
             dense=(False,) * 4)
    uncut, _ = ref._layer(plain._mm("none"), s, 0, layer, b, x)
    np.testing.assert_allclose(sum(parts) - 3 * alike, uncut, rtol=1e-4,
                               atol=1e-4)


def test_held_rows_counts_under_the_bias():
    params, tokens, bias = _model()
    cfg = dataclasses.replace(CFG, experts_held=(2, 4))
    params = jax.tree_util.tree_map(lambda t: t, params)
    for layer in params["layers"][1:]:
        layer["wi"], layer["wm"] = layer["wi"][2:6], layer["wm"][2:6]
    rows = gpt.held_rows(params, tokens, cfg, attn="dense",
                         router_bias=bias)
    _, loads = gpt.forward_features(params, tokens, cfg, attn="dense",
                                    router_bias=bias)
    assert rows.shape == (3,)
    np.testing.assert_array_equal(rows, loads[:, 2:6].sum(-1))
    assert (np.asarray(loads).sum(-1) == tokens.size * 2).all()


def test_what_walks_one_kind_of_layer_refuses_the_hybrid_stack_by_name():
    import optax
    from kungfu_tpu.comm.mesh import flat_mesh
    from kungfu_tpu.parallel import moe_gpt, pipeline
    from kungfu_tpu.serving import DecodeEngine
    params, tokens, _ = _model()
    words = "conv layers, dense and routed"
    with pytest.raises(ValueError, match=words):
        gpt.decode_step(params, CFG, gpt.init_kv_cache(CFG, 1),
                        jnp.int32(0), tokens[:1, 0])
    with pytest.raises(ValueError, match=words):
        DecodeEngine(params, CFG)
    with pytest.raises(ValueError, match=words):
        pipeline.make_gpt_pp_train_step(
            CFG, optax.sgd(0.1), flat_mesh(jax.devices()[:1]), n_micro=1)
    # each of the three alone: a conv layer, a mixed stack, the sigmoid
    attention = dataclasses.replace(CFG, operator="attn", router_reads="ln1")
    for cfg in (dataclasses.replace(CFG, n_experts=0, experts_per_token=0,
                                    routed=None),
                dataclasses.replace(attention, router="softmax"),
                dataclasses.replace(attention, routed=None)):
        with pytest.raises(ValueError, match=words):
            moe_gpt.forward_local(params, tokens, moe_gpt.MoEGPTConfig(
                gpt=cfg))


def test_the_configuration_says_no_to_what_the_layers_cannot_run():
    with pytest.raises(ValueError, match="operator"):
        dataclasses.replace(CFG, operator=("conv", "scan", "attn", "conv"))
    with pytest.raises(ValueError, match="reads ln2"):
        dataclasses.replace(CFG, router_reads="ln1")
    with pytest.raises(ValueError, match="routed layers need"):
        dataclasses.replace(CFG, n_experts=0, experts_per_token=0)
    with pytest.raises(ValueError, match="4 layers"):
        dataclasses.replace(CFG, routed=(True,))
    with pytest.raises(ValueError, match="attention's"):
        gpt.apply_layer(_model()[0]["layers"][0], jnp.zeros((1, 8, 64)),
                        CFG, index=0, remat_around_attn=True)
