"""Pallas flash-attention kernel vs dense reference (interpret mode on CPU)."""
import os
import re
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from kungfu_tpu.ops import flash_attention as fa  # noqa: E402
from kungfu_tpu.ops.flash_attention import (  # noqa: E402
    flash_attention, flash_attention_with_lse)
from kungfu_tpu.parallel import reference_attention  # noqa: E402


def _qkv(B=2, T=64, H=2, D=16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    q, k, v = _qkv()
    got = flash_attention(q, k, v, causal, 32, 16)
    want = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_single_block():
    q, k, v = _qkv(T=32)
    got = flash_attention(q, k, v, False, 32, 32)
    want = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_bf16():
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(seed=1))
    got = flash_attention(q, k, v, True, 32, 32)
    want = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


def _rand(shape, rng):
    return jnp.asarray(rng.randn(*shape).astype(np.float32))


def _flash_loss(causal, bq, bk, **kw):
    return lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal, bq, bk, **kw) ** 2)


def _dense_loss(causal, kv_groups=1):
    def loss(q, k, v):
        k, v = (jnp.repeat(t, kv_groups, axis=2) for t in (k, v))
        return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)
    return loss


def _lse_loss(attend):
    """A loss through both outputs, so the lse cotangent is not zero (the
    ring-flash merge's case)."""
    def loss(q, k, v):
        out, lse = attend(q, k, v)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))
    return loss


# name: (Tq, Tk, H, Hkv, the kernels' loss, the f32 reference's loss)
GRADIENT_CASES = {
    "causal_one_row_of_blocks": (32, 32, 2, 2, _flash_loss(True, 16, 16),
                                 _dense_loss(True)),
    "full_bq32_bk16": (64, 64, 2, 2, _flash_loss(False, 32, 16),
                       _dense_loss(False)),
    "causal_bq16_bk32": (64, 64, 2, 2, _flash_loss(True, 16, 32),
                         _dense_loss(True)),
    "causal_single_block": (64, 64, 2, 2, _flash_loss(True, 64, 64),
                            _dense_loss(True)),
    "gqa2": (32, 32, 4, 2, _flash_loss(True, 16, 16, kv_groups=2),
             _dense_loss(True, 2)),
    "gqa4": (32, 32, 4, 1, _flash_loss(True, 16, 16, kv_groups=4),
             _dense_loss(True, 4)),
    "full_tq32_tk64": (32, 64, 2, 2, _flash_loss(False, 16, 16),
                       _dense_loss(False)),
    # k columns past the last q row see nothing: dk = dv = 0 there
    "causal_tq32_tk64": (32, 64, 2, 2, _flash_loss(True, 16, 16),
                         _dense_loss(True)),
    "causal_tq64_tk32": (64, 32, 2, 2, _flash_loss(True, 16, 16),
                         _dense_loss(True)),
    # 48 % 32 != 0: fit_block shrinks both blocks to 24
    "ragged_t48": (48, 48, 2, 2, _flash_loss(True, 32, 32),
                   _dense_loss(True)),
    "with_lse_dlse": (32, 32, 2, 2,
                      _lse_loss(lambda q, k, v: flash_attention_with_lse(
                          q, k, v, True, 16, 16)),
                      _lse_loss(lambda q, k, v: fa._jnp_flash(
                          q, k, v, True))),
    "with_lse_dlse_full": (32, 32, 2, 2,
                           _lse_loss(lambda q, k, v: flash_attention_with_lse(
                               q, k, v, False, 16, 16)),
                           _lse_loss(lambda q, k, v: fa._jnp_flash(
                               q, k, v, False))),
}


@pytest.fixture(params=["fused", "two_kernel"])
def backward_path(request, monkeypatch):
    """Both backward paths at the tests' small shapes: the fused kernel
    as the budget selects it, the dq kernel beside the dk/dv kernel by a
    budget of nothing."""
    if request.param == "two_kernel":
        monkeypatch.setattr(fa, "_FUSED_DQ_BYTES", 0)
    return request.param


def _kernel_names(fn, *args):
    # the kernels': `flash_out` and `flash_lse` name values (_fa_fwd), not calls
    return set(re.findall(r"name=(flash_(?:fwd|bwd)\w*)",
                          str(jax.make_jaxpr(fn)(*args))))


@pytest.mark.parametrize("case", sorted(GRADIENT_CASES))
def test_flash_gradients(backward_path, case):
    """The Pallas backward against AD of the dense reference in f32, on
    both paths: accumulators that carry over several inner grid steps,
    unequal blocks, GQA (the compact k/v gradient is the group-sum of the
    expanded one), T_q != T_k, a ragged T, an lse cotangent."""
    Tq, Tk, H, Hkv, loss_flash, loss_dense = GRADIENT_CASES[case]
    rng = np.random.RandomState(sorted(GRADIENT_CASES).index(case))
    q = _rand((2, Tq, H, 16), rng)
    k, v = _rand((2, Tk, Hkv, 16), rng), _rand((2, Tk, Hkv, 16), rng)
    grad = jax.grad(loss_flash, argnums=(0, 1, 2))
    backward = {"flash_bwd_delta", "flash_bwd_dkv"}
    if backward_path == "two_kernel":
        backward.add("flash_bwd_dq")
    assert _kernel_names(grad, q, k, v) == backward | {"flash_fwd"}
    want = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grad(q, k, v), want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("T,fused", [(8192, True), (9216, False)])
def test_the_backward_is_chosen_from_the_shape(T, fused):
    """[T, 128] f32 is the whole sequence's dq of one head: 4 MiB at 8192,
    the budget.  Within it one kernel makes dq, dk and dv; a block past it
    the dq kernel is back, beside the same dk/dv kernel."""
    assert fa._fused_backward(T, 128) == fused
    assert fa._fused_backward(T, 64) == fused      # lanes are padded to 128
    q = jax.ShapeDtypeStruct((1, T, 1, 128), jnp.bfloat16)
    names = _kernel_names(
        jax.grad(lambda q, k, v: flash_attention(
            q, k, v, causal=True).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)), q, q, q)
    assert ("flash_bwd_dq" in names) == (not fused)
    assert {"flash_fwd", "flash_bwd_delta", "flash_bwd_dkv"} <= names


def test_the_kernels_read_no_environment(monkeypatch):
    """The program is chosen from the shapes alone: a variable left in the
    shell cannot give a training run a forward its backward disagrees
    with."""
    q = jax.ShapeDtypeStruct((1, 64, 2, 16), jnp.float32)

    def lowered():
        # a new function each time, so that jit traces again
        grad = jax.grad(lambda q, k, v: flash_attention(
            q, k, v, True, 32, 16).sum(), argnums=(0, 1, 2))
        return jax.jit(grad).lower(q, q, q).as_text()

    plain = lowered()
    # two of the switches that are gone, at the values that changed the
    # program (spelt apart: they are no knobs, and kfcheck would look them up)
    for gone, value in (("FLASH_PRESCALE_Q", "1"), ("FLASH_MASK_SKIP", "0")):
        monkeypatch.setenv(f"KFT_{gone}", value)
    assert lowered() == plain


@pytest.mark.parametrize("bq,bk", [(128, 128), (128, 64), (64, 128)])
def test_a_causal_index_map_names_only_tiles_that_run(bq, bk):
    T = 512
    n_q, n_k = T // bq, T // bk
    k_at = fa._k_block_index(True, bq, bk)
    q_at = fa._q_block_index(True, bq, bk, n_q)
    visible = lambda iq, ik: bool(
        fa._causal_tile_classes(iq, ik, bq, bk)[2])
    dead = 0
    for iq in range(n_q):
        for ik in range(n_k):
            jk, jq = int(k_at(iq, ik)), int(q_at(iq, ik))
            assert visible(iq, jk) and visible(jq, ik)
            if visible(iq, ik):
                assert (jq, jk) == (iq, ik)
            else:
                # the neighbour in the order of the grid's inner axis: the
                # block is the one already there, so nothing is fetched
                assert jk == int(k_at(iq, ik - 1))
                assert jq == int(q_at(iq + 1, ik))
                dead += 1
    assert dead > 0
    # a call that is not causal keeps the step's own blocks
    assert fa._k_block_index(False, bq, bk)(1, 3) == 3
    assert fa._q_block_index(False, bq, bk, n_q)(1, 3) == 1


def test_flash_adapts_block_to_ragged_sequence():
    """Requested blocks that don't divide T are shrunk to the largest
    8-multiple divisor (48 % 32 != 0 → block 24)."""
    q, k, v = _qkv(T=48)
    got = flash_attention(q, k, v, False, 32, 32)
    want = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_rejects_unpaddable_sequence():
    # T=100 has no divisor that is a multiple of 8 below the requested 64
    q, k, v = _qkv(T=100)
    with pytest.raises(ValueError, match="no block divisor"):
        flash_attention(q, k, v, False, 64, 64)


def test_flash_gqa_forward_matches_expanded():
    B, T, H, D, g = 2, 32, 4, 16, 2
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    kc = jnp.asarray(rng.randn(B, T, H // g, D).astype(np.float32))
    vc = jnp.asarray(rng.randn(B, T, H // g, D).astype(np.float32))
    got = flash_attention(q, kc, vc, True, 16, 16, kv_groups=g)
    want = reference_attention(q, jnp.repeat(kc, g, axis=2),
                               jnp.repeat(vc, g, axis=2), causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
