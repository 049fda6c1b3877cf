"""The expert layer without dropped tokens (parallel/moe.py): the grouped
product against `jax.lax.ragged_dot`, the rows it multiplies, routing under
exact ties, the shares of the experts adding up to the whole layer, and the
layer inside models/gpt.py (a head size of its own, layers of two kinds, the
probe that counts the held rows, the scopes in the lowered step)."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from kungfu_tpu.comm.mesh import PEER_AXIS, flat_mesh
from kungfu_tpu.models import gpt
from kungfu_tpu.parallel import moe


def _product(sizes, M=64, K=8, N=5, seed=1):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (M, K)),
            jax.random.normal(keys[1], (len(sizes), K, N)),
            jax.random.normal(keys[2], (M, N)),
            jnp.asarray(sizes, jnp.int32))


# groups that end inside a block, an empty group, blocks of one group each,
# nothing held, everything in one group, groups smaller than a block, a
# buffer shorter than a block
SIZES = [([10, 0, 7, 20], 16), ([16, 16, 16, 16], 16), ([0, 0, 0, 0], 16),
         ([64, 0, 0, 0], 16), ([1, 1, 1, 33], 8), ([3, 40, 2], 128)]


@pytest.mark.parametrize("sizes, block", SIZES)
def test_the_grouped_product_is_ragged_dot(sizes, block):
    rows, weights, c, gs = _product(sizes)
    mine = lambda r, w: jnp.sum(c * moe.grouped_matmul(r, w, gs, block))
    xla = lambda r, w: jnp.sum(c * jax.lax.ragged_dot(
        r, w, gs, precision="highest"))
    np.testing.assert_allclose(
        moe.grouped_matmul(rows, weights, gs, block),
        jax.lax.ragged_dot(rows, weights, gs, precision="highest"),
        rtol=1e-5, atol=1e-5)
    got = jax.jit(jax.grad(mine, (0, 1)))(rows, weights)
    want = jax.grad(xla, (0, 1))(rows, weights)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=2e-5)


@pytest.mark.parametrize("sizes, block", SIZES + [
    # a cell's shape in small: 16 held experts, 96 blocks of buffer, a
    # quarter of the assignments held, unevenly
    ([13, 40, 7, 22, 31, 9, 0, 25, 18, 27, 11, 36, 5, 29, 16, 19], 8)])
def test_the_product_multiplies_the_held_rows_and_under_a_block_a_group_more(
        sizes, block):
    M = max(64, 4 * sum(sizes))
    M += -M % block
    block = min(block, M)
    visits, group, blk, starts, ends = moe._visits(
        jnp.asarray(sizes, jnp.int32), M, block)
    multiplied, held = int(visits) * block, sum(sizes)
    # the held rows and, over all the groups, less than one block a held
    # (non-empty) group more; never the buffer's 4 x held rows
    assert held <= multiplied
    assert multiplied < held + block * sum(1 for n in sizes if n) or not held
    assert multiplied == 0 or len(sizes) < 16 or multiplied < M // 2
    # every held row lies in a visit of its own group
    seen = np.zeros(M, bool)
    for v in range(int(visits)):
        g, at = int(group[v]), int(blk[v]) * block
        r = np.arange(at, at + block)
        seen[r] |= (r >= int(starts[g])) & (r < int(ends[g]))
    assert seen.sum() == held and seen[:held].all()


@pytest.mark.parametrize("seed, held, block", [
    (0, (0, 16), 8), (1, (16, 16), 8), (2, (48, 16), 16), (3, (5, 3), 4),
    (4, (0, 64), 8), (5, (60, 4), 64)])
def test_a_block_of_the_row_buffer_holds_one_experts_rows_in_token_order(
        seed, held, block):
    """What the products' work rests on: whole blocks of one expert."""
    n, k, E = 96, 6, 64
    logits = jax.random.normal(jax.random.PRNGKey(seed), (n, E))
    ids = jax.lax.top_k(logits, k)[1].astype(jnp.int32)
    sizes, n_rows, tok, which, live, dest, here = map(
        np.asarray, moe.row_buffer(ids, n, held, block))
    flat = np.asarray(ids).reshape(-1)
    count = np.array([(flat == held[0] + g).sum() for g in range(held[1])])
    assert (sizes == -(-count // block) * block).all()
    assert n_rows == sizes.sum() < count.sum() + held[1] * block
    assert len(tok) == (-(-n * k // block) + held[1]) * block
    assert live.sum() == count.sum() == here.sum()
    assert not live[n_rows:].any()
    for at in range(0, int(n_rows), block):
        rows = slice(at, at + block)
        lv, tk = live[rows], tok[rows]
        # the live rows first, of one expert, their tokens rising (a token
        # has one row an expert at most); then padding, naming token 0
        assert lv.any() and not lv[np.argmin(lv):].any() or lv.all()
        assert len(set(flat[which[rows]][lv])) == 1
        assert (np.diff(tk[lv]) > 0).all() and (tk[~lv] == 0).all()
        assert (tk[lv] == which[rows][lv] // k).all()
    # the way back: a held assignment's row stands for that assignment
    assert (which[dest[here]] == np.flatnonzero(here)).all()
    assert live[dest[here]].all()


def test_top_k_under_exact_ties_takes_the_lower_ids():
    # a router of zeros: every expert equally probable for every token
    ids, weights = moe.route_topk(jnp.ones((5, 8)), jnp.zeros((8, 16)), 6)
    assert ids.tolist() == [[0, 1, 2, 3, 4, 5]] * 5
    np.testing.assert_allclose(weights, 1 / 6, rtol=1e-6)
    # two tied at the cut: the lower id is in, the higher out
    router = jnp.zeros((8, 16)).at[:, [2, 9, 11]].set(1.0).at[
        :, [4, 13]].set(0.5)
    ids, _ = moe.route_topk(jnp.ones((3, 8)), router, 4)
    assert ids.tolist() == [[2, 9, 11, 4]] * 3


def _layer_params(E=8, D=24, F=16, seed=4):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return ({"router": jax.random.normal(k[0], (D, E)),
             "wi": jax.random.normal(k[1], (E, D, 2 * F)) / np.sqrt(D),
             "wm": jax.random.normal(k[2], (E, F, D)) / np.sqrt(F)},
            jax.random.normal(k[3], (2, 40, D)))


def _by_hand(params, x, k, held):
    """The layer in NumPy, a token and an expert at a time."""
    p, x = jax.tree_util.tree_map(np.asarray, params), np.asarray(x)
    flat = x.reshape(-1, x.shape[-1]).astype(np.float64)
    logits = flat @ p["router"].astype(np.float64)
    prob = np.exp(logits - logits.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    out = np.zeros_like(flat)
    for t in range(flat.shape[0]):
        top = np.argsort(-prob[t], kind="stable")[:k]
        for e in top:
            if held[0] <= e < held[0] + held[1]:
                g = (flat[t] @ p["wi"][e - held[0]]).reshape(2, -1)
                out[t] += (prob[t, e] / prob[t, top].sum()) * (
                    (np.maximum(g[0], 0) * g[1]) @ p["wm"][e - held[0]])
    return out.reshape(x.shape)


@pytest.mark.parametrize("held", [(0, 8), (2, 4), (6, 2)])
def test_the_layer_is_the_sum_over_a_tokens_held_experts(held):
    params, x = _layer_params()
    mine = dict(params, wi=params["wi"][held[0]:held[0] + held[1]],
                wm=params["wm"][held[0]:held[0] + held[1]])
    got = moe.dropless_moe_ffn(mine, x, experts_per_token=3, held=held,
                               block_rows=16)
    np.testing.assert_allclose(got, _by_hand(mine, x, 3, held), rtol=2e-4,
                               atol=2e-4)


def test_groups_of_whole_blocks_need_no_mask():
    rows, weights, c, gs = _product([16, 0, 32, 16], M=96)
    prod = lambda r, w: jnp.sum(c * moe.grouped_matmul(
        r, w, gs, 16, whole_blocks=True))
    xla = lambda r, w: jnp.sum(c * jax.lax.ragged_dot(
        r, w, gs, precision="highest"))
    np.testing.assert_allclose(
        moe.grouped_matmul(rows, weights, gs, 16, whole_blocks=True),
        jax.lax.ragged_dot(rows, weights, gs, precision="highest"),
        rtol=1e-5, atol=1e-5)
    for g, w in zip(jax.grad(prod, (0, 1))(rows, weights),
                    jax.grad(xla, (0, 1))(rows, weights)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=2e-5)


def test_the_shares_add_up_to_the_whole_layer():
    """Four chips' parts of the result, each from its own quarter of the
    experts, sum to what one chip that holds them all computes, and to the
    plain reference's layer given every expert."""
    from perf.reference import gpt as plain, smallthinker as ref
    params, x = _layer_params()
    whole = moe.dropless_moe_ffn(params, x, experts_per_token=3,
                                 block_rows=16)
    parts = [moe.dropless_moe_ffn(
        dict(params, wi=params["wi"][a:a + 2], wm=params["wm"][a:a + 2]), x,
        experts_per_token=3, held=(a, 2), block_rows=16)
        for a in (0, 2, 4, 6)]
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-4)
    flat = x.reshape(-1, x.shape[-1])
    w = ref.routing_weights(jax.nn.softmax(jnp.einsum(
        "td,de->te", flat, params["router"], precision="highest"), -1), 3,
        True)
    uncut = ref.held_experts(plain._mm("none"), {"first": 0, "G": 8},
                             params, flat, w)
    np.testing.assert_allclose(sum(parts).reshape(flat.shape), uncut,
                               rtol=1e-4, atol=1e-4)


def _dense_sum(x, ids, weights, wi, wm, held, act="reglu"):
    """The benchmark's plain reference: each held expert over every token,
    weighted by what the routing sends it (nought for most); ReGLU experts
    are the sparse family's, SwiGLU ones the hybrid family's."""
    from perf.reference import gpt as plain, lfm2, smallthinker
    sent = jnp.einsum("nk,nke->ne", weights, jax.nn.one_hot(ids, 8))
    ref = smallthinker if act == "reglu" else lfm2
    return ref.held_experts(plain._mm("none"),
                            {"first": held[0], "G": held[1]},
                            {"wi": wi, "wm": wm}, x, sent)


def _routed(name):
    """``(ids [n, 3], held, block_rows)`` of the eight experts' layer."""
    n, k, E = 40, 3, 8
    logits = jax.random.normal(jax.random.PRNGKey(7), (n, E))
    top = lambda l: jax.lax.top_k(l, k)[1].astype(jnp.int32)
    if name == "a held expert without a row":
        return top(logits.at[:, 3].set(-1e9)), (2, 4), 16
    if name == "a group of exactly one block":
        ids = top(logits.at[:16, 5].set(1e9).at[16:, 5].set(-1e9))
        assert int((ids == 5).sum()) == 16
        return ids, (2, 4), 16
    if name == "every assignment held elsewhere":
        return top(logits.at[:, 6:].set(-1e9)), (6, 2), 16
    if name == "fewer assignments than a block":
        return top(logits), (0, 8), 512
    return top(logits), name, 16


def _under(how, layer, c):
    """``layer(x, weights, wi, wm)`` and the gradient of ``sum(c * layer)``
    by each of the four, as a train step takes them."""
    if how == "checkpoint":
        layer = jax.checkpoint(layer, policy=gpt._FULL_REMAT_KEEPS)

    def both(*a):
        return layer(*a), jax.grad(lambda *a: jnp.sum(c * layer(*a)),
                                   (0, 1, 2, 3))(*a)
    if how == "shard_map":
        # as training.build_train_step: the arguments stacked over the
        # mesh's one axis and unstacked inside, so every one varies over
        # it, and the gradient taken inside
        stack = lambda t: jax.tree_util.tree_map(lambda v: v[None], t)
        sharded = jax.shard_map(
            lambda *a: stack(both(*(t[0] for t in a))),
            mesh=flat_mesh(n=1), in_specs=P(PEER_AXIS), out_specs=P(PEER_AXIS))
        return lambda *a: jax.tree_util.tree_map(
            lambda v: v[0], sharded(*stack(a)))
    return both


@pytest.mark.parametrize("act", ["reglu", "swiglu"])
@pytest.mark.parametrize("how", ["plain", "checkpoint", "shard_map"])
@pytest.mark.parametrize("case", [
    (0, 8), (2, 4), (6, 2), "a held expert without a row",
    "a group of exactly one block", "every assignment held elsewhere",
    "fewer assignments than a block"], ids=str)
def test_the_one_loop_and_its_backward_are_the_dense_sum(case, how, act):
    """Value and the gradient of every input, at the edges the loop over
    the blocks in use has, for either gate: the written-out backward
    against jax's gradient of the plain loop over the experts."""
    ids, held, block = _routed(case)
    params, x = _layer_params()
    k = jax.random.split(jax.random.PRNGKey(9), 2)
    x = x.reshape(-1, x.shape[-1])[:ids.shape[0]]
    weights = jax.random.uniform(k[0], ids.shape, minval=0.1)
    wi, wm = (params[w][held[0]:held[0] + held[1]] for w in ("wi", "wm"))
    c = jax.random.normal(k[1], x.shape)
    got = jax.jit(_under(how, lambda *a: moe.expert_ffn(
        a[0], ids, *a[1:], held, block, act=act), c))(x, weights, wi, wm)
    want = jax.jit(_under("plain", lambda *a: _dense_sum(
        a[0], ids, *a[1:], held, act), c))(x, weights, wi, wm)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
    if case == "every assignment held elsewhere":
        assert not any(np.asarray(g).any()
                       for g in jax.tree_util.tree_leaves(got))


def test_the_backward_keeps_the_inputs_and_no_row_of_the_buffer():
    """The mechanism: what the layer saves for its backward is its inputs,
    the buffer's integer arrays and one scale a row, so nothing [M, .] is
    filled, written and read back, and a checkpoint's backward has no
    forward loop to run again."""
    ids, held, block = _routed((2, 4))
    params, x = _layer_params()
    x = x.reshape(-1, x.shape[-1])[:ids.shape[0]]
    wi, wm = (params[w][2:6] for w in ("wi", "wm"))
    M = (-(-ids.size // block) + held[1]) * block
    # (what `jax.vjp` hands back is a pytree of what it kept)
    kept = jax.tree_util.tree_leaves(jax.vjp(
        lambda *a: moe.expert_ffn(a[0], ids, *a[1:], held, block),
        x, jnp.ones(ids.shape), wi, wm)[1])
    floats = [a.shape for a in kept if jnp.issubdtype(a.dtype, jnp.floating)]
    assert sorted(floats) == sorted([x.shape, wi.shape, wm.shape, (M,)])
    assert M not in (x.shape[0], wi.shape[0], wm.shape[0])


def test_a_router_placed_before_attention_reads_another_tensor():
    params, x = _layer_params()
    other = x[::-1]
    got = moe.dropless_moe_ffn(params, x, experts_per_token=3,
                               router_input=other, block_rows=16)
    ids, weights = moe.route_topk(other.reshape(-1, x.shape[-1]),
                                  params["router"], 3)
    want = moe.expert_ffn(x.reshape(-1, x.shape[-1]), ids, weights,
                          params["wi"], params["wm"], (0, 8), 16)
    np.testing.assert_allclose(got.reshape(want.shape), want, rtol=1e-6)


# ----------------------------------------------------- inside models/gpt.py
CFG = gpt.GPTConfig(
    vocab_size=64, d_model=40, n_heads=6, d_head=8, n_kv_heads=2, n_layers=4,
    d_ff=0, max_seq=64, dtype=jnp.float32, rope=(False, True, True, True),
    window=(None, 16, 16, 16), mlp="reglu", n_experts=8, experts_per_token=3,
    d_expert=12, experts_held=(2, 4), norm_eps=1e-6, rope_theta=1.5e6)


def _model():
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 64)
    return gpt.init_params(jax.random.PRNGKey(0), CFG), tokens


def test_a_head_size_of_its_own_and_the_held_experts_shapes():
    params, _ = _model()
    layer = params["layers"][0]
    assert CFG.head_dim == 8 and CFG.d_model % CFG.n_heads != 0
    assert layer["wq"].shape == (40, 6, 8) and layer["wo"].shape == (6, 8, 40)
    assert layer["router"].shape == (40, 8)         # over all the experts
    assert layer["wi"].shape == (4, 40, 2 * 12)     # of those held
    assert layer["wm"].shape == (4, 12, 40)
    assert "wpe" not in params
    with pytest.raises(ValueError, match="not divisible"):
        gpt.GPTConfig(d_model=40, n_heads=6)
    with pytest.raises(ValueError, match="entries for"):
        dataclasses.replace(CFG, window=(None, 16))
    with pytest.raises(ValueError, match="routed feed-forward"):
        dataclasses.replace(CFG, experts_held=(6, 4))


@pytest.mark.parametrize("remat", ["", "full"])
def test_the_kernels_path_is_the_dense_path(remat):
    params, tokens = _model()
    loss = lambda attn: lambda p: jnp.mean(gpt.forward_features(
        p, tokens, CFG, attn=attn, remat=remat) ** 2)
    # each side one compiled program: op by op they take several times as
    # long
    got = jax.jit(jax.value_and_grad(loss("flash")))(params)
    want = jax.jit(jax.value_and_grad(loss("dense")))(params)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)


def test_a_nope_layer_beside_a_rope_layer():
    """Layer 0 rotates nothing: moving every token along by one changes the
    RoPE layers' view of nothing (rotation is relative) and the NoPE
    layer's of nothing either, so features shift with the tokens; a model
    that rotated by absolute position only in SOME layers would too, so the
    kinds are also told apart directly: q of layer 0 is the projection."""
    params, tokens = _model()
    layer, x = params["layers"][0], gpt.embed(params, tokens, None, CFG)
    pos = jnp.arange(64)
    q0, k0, _ = gpt._layer_qkv(layer, x, CFG, pos=pos, rope=CFG.layer_rope(0))
    q1, k1, _ = gpt._layer_qkv(layer, x, CFG, pos=pos, rope=CFG.layer_rope(1))
    h = gpt.rms_norm(x, layer["ln1"], CFG.norm_eps)
    plain = jnp.einsum("btd,dhk->bthk", h, layer["wq"])
    np.testing.assert_allclose(q0, plain, rtol=1e-6)
    assert float(jnp.max(jnp.abs(q1[:, 1:] - plain[:, 1:]))) > 1e-2
    np.testing.assert_allclose(q1[:, 0], plain[:, 0], rtol=1e-6)  # angle 0
    assert [CFG.layer_rope(i) for i in range(4)] == [False, True, True, True]
    assert [CFG.layer_window(i) for i in range(4)] == [None, 16, 16, 16]


def test_a_window_shorter_than_the_sequence_changes_the_result():
    params, tokens = _model()
    full = dataclasses.replace(CFG, window=None)
    run = lambda cfg: jax.jit(lambda p, t: gpt.forward_features(
        p, t, cfg, attn="dense"))(params, tokens)
    a, b = run(CFG), run(full)
    np.testing.assert_allclose(a[:, :16], b[:, :16], rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(a[:, 32:] - b[:, 32:]))) > 1e-3


def test_held_rows_is_a_count_made_in_numpy():
    params, tokens = _model()
    got = jax.jit(lambda p, t: gpt.held_rows(p, t, CFG, attn="dense"))(
        params, tokens)
    x = gpt.embed(params, tokens, None, CFG)
    pos, want = jnp.arange(64), []
    for i, layer in enumerate(params["layers"]):
        h = np.asarray(gpt.rms_norm(x, layer["ln1"], CFG.norm_eps),
                       np.float64).reshape(-1, 40)
        logits = h @ np.asarray(layer["router"], np.float64)
        top = np.argsort(-logits, axis=-1, kind="stable")[:, :3]
        want.append(int(((top >= 2) & (top < 6)).sum()))
        x = gpt.apply_layer(layer, x, CFG, attn="dense", pos=pos, index=i)
    assert got.tolist() == want
    assert 0 < min(want) and max(want) < 2 * 64 * 3     # some, never all


def test_the_scopes_stand_in_the_lowered_step():
    params, tokens = _model()
    step = jax.jit(jax.grad(lambda p: jnp.mean(gpt.forward_features(
        p, tokens, CFG, attn="dense", remat="full") ** 2)))
    text = step.lower(params).as_text(debug_info=True)
    # the router stands beside attention's projections, under no `attn`;
    # the experts' scopes stand inside the one loop a pass, the forward's
    # and the backward's that the layer writes out itself, and each pass
    # adds into the tokens with the kernel, not with XLA's scatter
    for scope in ("jvp(ffn)/moe/moe_route", "/moe/moe_route/top_k",
                  "rematted_computation/ffn/moe/moe_route",
                  "jvp(ffn)/moe/while/body/gmm",
                  "jvp(ffn)/moe/while/body/moe_act",
                  "jvp(ffn)/moe/while/body/moe_route/moe_scatter_add",
                  "checkpoint/ffn/moe/while/body/gmm",
                  "checkpoint/ffn/moe/while/body/moe_act",
                  "checkpoint/ffn/moe/while/body/moe_route/moe_scatter_add"):
        assert scope in text, scope
    assert "while/body/moe_route/scatter-add" not in text
    # full remat routes again and has no forward loop to run again
    assert "rematted_computation/ffn/moe/while" not in text


def test_what_walks_one_kind_of_layer_refuses_this_model_by_name():
    params, tokens = _model()
    with pytest.raises(ValueError, match="layers of one kind"):
        gpt.decode_step(params, CFG, gpt.init_kv_cache(CFG, 1),
                        jnp.int32(0), tokens[:1, 0])
    with pytest.raises(ValueError, match="no sliding window"):
        gpt._attend(None, None, None, "ring", "sp", window=16)
    with pytest.raises(ValueError, match="expert model"):
        gpt.held_rows(params, tokens, dataclasses.replace(
            CFG, n_experts=0, experts_per_token=0, mlp="gelu"))


def test_reglu_is_the_dense_feed_forwards_third_kind():
    cfg = gpt.GPTConfig(vocab_size=32, d_model=16, n_heads=2, n_layers=1,
                        d_ff=24, max_seq=8, dtype=jnp.float32, rope=True,
                        mlp="reglu")
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    layer = params["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 16))
    want = (jax.nn.relu(h @ layer["wi"][:, 0]) * (h @ layer["wi"][:, 1])
            ) @ layer["wm"]
    np.testing.assert_allclose(gpt._dense_ffn(layer, h, cfg), want,
                               rtol=1e-5, atol=1e-6)
