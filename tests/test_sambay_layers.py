"""The SambaY layers of models/sambay.py and their kernels, at sizes the CPU
holds (the kernels interpreted): the selective scan against a scan one
position at a time, forward and backward over several chunks and channel
blocks; flash attention with values twice the width of the keys against
dense attention; the memory and the shared KV reach the layers that read
them and no other; and every path that walks layers of one kind refuses
these by name."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kungfu_tpu.models import gpt, sambay
from kungfu_tpu.ops import flash_attention as fa
from kungfu_tpu.ops import selective_scan as ss

L = 6
# the cut's six kinds: mamba, windowed differential attention, the mamba
# that makes the memory, the full differential attention whose K and V are
# shared, a gated memory unit and a cross-attention layer; 8 query and 4 KV
# heads of 16, values of 32
CFG = gpt.GPTConfig(
    vocab_size=96, d_model=64, n_heads=8, n_kv_heads=4, d_head=16,
    n_layers=L, d_ff=96, max_seq=64, dtype=jnp.float32, rope=(False,) * L,
    mlp="swiglu", window=(None, 16, None, None, None, None),
    operator=("mamba", "diff", "mamba", "diff", "gmu", "cross"),
    norm="layer", first_layer=14)


def _scan_inputs(b, T, ch, N=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (b, T, ch)),
            jax.nn.softplus(jax.random.normal(k[1], (b, T, ch)) - 1.0),
            -jnp.exp(0.5 * jax.random.normal(k[2], (ch, N))),
            jax.random.normal(k[3], (b, T, N)),
            jax.random.normal(k[4], (b, T, N)),
            jax.random.normal(k[5], (b, T, ch)))


@pytest.mark.parametrize("T, ch, chunk, block", [
    (256, 256, 128, 128),     # two chunks, two channel blocks
    (384, 128, 128, 128),     # one block, three chunks
    (48, 64, 48, 64)])        # one chunk and one block, neither whole lanes
def test_the_scan_kernel_is_the_scan_one_position_at_a_time(
        T, ch, chunk, block, monkeypatch):
    monkeypatch.setattr(ss, "CHUNK", chunk)
    monkeypatch.setattr(ss, "BLOCK", block)
    assert ss.blocks(T, ch) == (chunk, block)
    u, delta, A, B, C, dy = _scan_inputs(2, T, ch)
    y, vjp = jax.vjp(ss.selective_scan, u, delta, A, B, C)
    want, want_vjp = jax.vjp(ss.scan_reference, u, delta, A, B, C)
    # float32 both sides, the same sums in another order: a few ulps of
    # the largest value
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(y, want, rtol=0, atol=1e-5 * scale)
    for name, got, w in zip("u delta A B C".split(), vjp(dy), want_vjp(dy)):
        np.testing.assert_allclose(got, w, rtol=0,
                                   atol=1e-5 * float(jnp.max(jnp.abs(w))),
                                   err_msg=name)


def test_the_scan_is_causal_and_starts_from_nought():
    u, delta, A, B, C, _ = _scan_inputs(1, 64, 64)
    y = ss.selective_scan(u, delta, A, B, C)
    moved = ss.selective_scan(u.at[:, 40].add(1.0), delta, A, B, C)
    np.testing.assert_array_equal(y[:, :40], moved[:, :40])
    assert float(jnp.max(jnp.abs(y[:, 40] - moved[:, 40]))) > 1e-3
    # at the first position the state is (delta u) B alone
    first = jnp.sum((delta[0, 0] * u[0, 0])[:, None] * B[0, 0] * C[0, 0], -1)
    np.testing.assert_allclose(y[0, 0], first, rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="eight at a time"):
        ss.blocks(60, 64)


@pytest.mark.parametrize("fused", [True, False])
@pytest.mark.parametrize("window", [None, 48])
def test_flash_with_values_twice_the_keys_width_is_dense_attention(
        fused, window, monkeypatch):
    if not fused:       # the dq kernel beside the dk/dv kernel, as at 16k
        monkeypatch.setattr(fa, "_FUSED_DQ_BYTES", 0)
    from kungfu_tpu.parallel.ring_attention import reference_attention
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(k[0], (1, 128, 4, 64))
    kk = jax.random.normal(k[1], (1, 128, 2, 64))
    v = jax.random.normal(k[2], (1, 128, 2, 128))
    g = jax.random.normal(k[3], (1, 128, 4, 128))
    flash = lambda q, kk, v: fa.flash_attention(
        q, kk, v, causal=True, kv_groups=2, block_q=32, block_k=32,
        window=window)
    dense = lambda q, kk, v: reference_attention(
        q, fa._expand_kv_heads(kk, 2), fa._expand_kv_heads(v, 2),
        causal=True, window=window)
    out, vjp = jax.vjp(flash, q, kk, v)
    want, want_vjp = jax.vjp(dense, q, kk, v)
    assert out.shape == (1, 128, 4, 128)
    # float32 online softmax against the whole one: 1e-5 of unit inputs
    np.testing.assert_allclose(out, want, rtol=0, atol=1e-5)
    for got, w in zip(vjp(g), want_vjp(g)):
        np.testing.assert_allclose(got, w, rtol=0, atol=2e-5)


@functools.partial(jax.jit, static_argnames=("attn", "carry_at", "change"))
def _stack(params, x, attn="dense", carry_at=None, change=None):
    """Each layer's output in turn; ``change(carry)`` replaces the carry
    after layer ``carry_at``."""
    outs, carry = [], {}
    for i, layer in enumerate(params["layers"]):
        x, carry = sambay.apply_layer(layer, x, carry, CFG, index=i,
                                      attn=attn)
        if i == carry_at:
            carry = change(carry)
        outs.append(x)
    return outs


def _init(cfg):
    """The weights, made in one program: op by op takes twice as long."""
    return jax.jit(functools.partial(gpt.init_params, cfg=cfg))(
        jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def params():
    return _init(CFG)


def _nudge(what, carry):
    return dict(carry, **{what: jax.tree_util.tree_map(lambda t: t + 0.5,
                                                        carry[what])})


@pytest.mark.parametrize("what, at, reader", [("memory", 2, 4),
                                              ("kv", 3, 5)])
def test_the_memory_and_the_shared_kv_reach_their_reader_alone(
        params, what, at, reader):
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 32, 64))
    plain = _stack(params, x)
    moved = _stack(params, x, carry_at=at, change=functools.partial(
        _nudge, what))
    for i in range(reader):         # nothing before the reader moves
        np.testing.assert_array_equal(plain[i], moved[i])
    assert float(jnp.max(jnp.abs(plain[reader] - moved[reader]))) > 1e-3


def test_what_walks_one_kind_of_layer_refuses_these_layers_by_name(params):
    import optax
    from kungfu_tpu.comm.mesh import flat_mesh
    from kungfu_tpu.parallel import moe_gpt, pipeline
    from kungfu_tpu.serving import DecodeEngine
    words = "mamba layers .a scan's state., diff layers"
    with pytest.raises(ValueError, match=words):
        gpt.decode_step(params, CFG, gpt.init_kv_cache(CFG, 1),
                        jnp.int32(0), jnp.zeros((1,), jnp.int32))
    with pytest.raises(ValueError, match=words):
        DecodeEngine(params, CFG)
    with pytest.raises(ValueError, match=words):
        pipeline.make_gpt_pp_train_step(
            CFG, optax.sgd(0.1), flat_mesh(jax.devices()[:1]), n_micro=1)
    with pytest.raises(ValueError, match=words):
        moe_gpt.forward_local(params, jnp.zeros((1, 64), jnp.int32),
                              moe_gpt.MoEGPTConfig(gpt=CFG))
    # each alone: a mamba layer beside plain attention, LayerNorm alone
    samba = dataclasses.replace(CFG, operator=("mamba", "attn") * 3,
                                norm="rms")
    for cfg in (samba, dataclasses.replace(CFG, operator="attn")):
        with pytest.raises(ValueError, match=words):
            gpt._attention_layers_only(cfg, "decode")
    with pytest.raises(ValueError, match="cross, diff, gmu, mamba layers "
                                         "run unsharded"):
        gpt.layer_stack(params, jnp.zeros((1, 64), jnp.int32), CFG,
                        remat="attn")


def test_mamba_layers_stand_beside_plain_attention_under_rms_norms():
    """A Samba stack: each operator is its layer's own, and the norm the
    configuration's, whatever else the stack holds."""
    samba = dataclasses.replace(CFG, n_layers=2, operator=("mamba", "attn"),
                                norm="rms", rope=(False, True), window=None)
    params = gpt.init_params(jax.random.PRNGKey(0), samba)
    assert "ln1_b" not in params["layers"][0] and "lnf_b" not in params
    assert set(params["layers"][1]) >= {"wq", "wk", "wv", "wo"}
    assert params["layers"][1]["wv"].shape == (64, 4, 16)   # not doubled
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0, 96)
    feats = gpt.forward_features(params, tokens, samba, attn="dense")
    assert feats.shape == (1, 32, 64) and bool(jnp.isfinite(feats).all())


def test_the_memory_and_the_kv_come_from_the_last_writers_before_readers():
    assert (sambay.memory_layer(CFG), sambay.kv_layer(CFG)) == (2, 3)
    assert sambay.dt_rank(CFG) == 4
    later = dataclasses.replace(CFG, operator=(
        "mamba", "diff", "gmu", "mamba", "diff", "cross"))
    assert (sambay.memory_layer(later), sambay.kv_layer(later)) == (0, 4)


@pytest.mark.parametrize("change, words", [
    (dict(operator=("gmu", "diff", "mamba", "diff", "gmu", "cross")),
     "a mamba layer must come before the first gmu"),
    (dict(operator=("mamba", "cross", "mamba", "diff", "gmu", "cross")),
     "a diff layer must come before the first cross"),
    # plain attention shares no KV of the differential layers' shape
    (dict(operator=("mamba", "attn", "mamba", "attn", "gmu", "cross")),
     "a diff layer must come before the first cross"),
    (dict(n_experts=4, experts_per_token=2, d_expert=32), "no experts"),
    (dict(rope=True), "without positions"),
    (dict(n_kv_heads=1, n_heads=3), "must be even"),
    (dict(norm="group"), "norm must be"),
])
def test_the_configuration_says_no_to_what_the_layers_cannot_run(change,
                                                                 words):
    with pytest.raises(ValueError, match=words):
        dataclasses.replace(CFG, **change)
