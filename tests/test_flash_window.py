"""The flash kernels' sliding window against dense masked attention
(interpret mode on the CPU): forward, the fused backward and the two-kernel
backward, at windows that are and are not a multiple of the block, and the
index maps that keep a grid step from fetching a tile outside the band."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kungfu_tpu.ops import flash_attention as fa
from kungfu_tpu.parallel import reference_attention

T, BLOCK = 128, 32


def _inputs(groups=2, H=4, D=16):
    rng = np.random.RandomState(3)
    mk = lambda h: jnp.asarray(rng.randn(1, T, h, D).astype(np.float32))
    return mk(H), mk(H // groups), mk(H // groups), mk(H)


def _dense(q, k, v, window, groups):
    return reference_attention(q, fa._expand_kv_heads(k, groups),
                               fa._expand_kv_heads(v, groups), causal=True,
                               window=window)


# a multiple of the block, not a multiple, under one block, one position,
# the whole sequence, longer than the sequence
WINDOWS = [64, 50, 7, 1, T, 4 * T]


@pytest.mark.parametrize("window", WINDOWS)
def test_the_windowed_forward_is_dense_masked_attention(window):
    q, k, v, _ = _inputs()
    got = fa.flash_attention(q, k, v, causal=True, block_q=BLOCK,
                             block_k=BLOCK, kv_groups=2, window=window)
    np.testing.assert_allclose(got, _dense(q, k, v, window, 2), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("fused", [True, False],
                         ids=["one_kernel", "dq_beside_dkv"])
@pytest.mark.parametrize("window", WINDOWS)
def test_the_windowed_backward_is_dense_masked_attentions(window, fused,
                                                           monkeypatch):
    if not fused:       # T past the budget: the dq kernel beside dk/dv
        monkeypatch.setattr(fa, "_FUSED_DQ_BYTES", 0)
    q, k, v, c = _inputs()
    flash = lambda q, k, v: jnp.sum(c * fa.flash_attention(
        q, k, v, causal=True, block_q=BLOCK, block_k=BLOCK, kv_groups=2,
        window=window))
    dense = lambda q, k, v: jnp.sum(c * _dense(q, k, v, window, 2))
    got = jax.grad(flash, (0, 1, 2))(q, k, v)
    want = jax.grad(dense, (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


def test_the_jnp_twin_takes_the_same_window():
    q, k, v, _ = _inputs(groups=1)
    out, lse = fa._jnp_flash(q, k, v, True, 50)
    np.testing.assert_allclose(out, _dense(q, k, v, 50, 1), rtol=2e-5,
                               atol=2e-5)
    assert lse.shape == (1, 4, T)


@pytest.mark.parametrize("window", [64, 50, 7])
def test_no_grid_step_names_a_tile_outside_the_band(window):
    n = T // BLOCK
    k_at = fa._k_block_index(True, BLOCK, BLOCK, window)
    q_at = fa._q_block_index(True, BLOCK, BLOCK, n, window)
    for iq in range(n):
        for ik in range(n):
            _, _, visible = fa._causal_tile_classes(iq, ik, BLOCK, BLOCK,
                                                    window)
            # a visible tile is fetched as itself; a dead step names a
            # visible tile of its row (forward) or of its column (backward)
            if visible:
                assert int(k_at(iq, ik)) == ik and int(q_at(iq, ik)) == iq
            assert bool(fa._causal_tile_classes(
                iq, int(k_at(iq, ik)), BLOCK, BLOCK, window)[2])
            assert bool(fa._causal_tile_classes(
                int(q_at(iq, ik)), ik, BLOCK, BLOCK, window)[2])


def test_the_tile_classes_by_hand():
    # block 32, window 50: tile (3, 1) holds queries 96..127, keys 32..63;
    # the nearest pair is 96 - 63 = 33 < 50 (visible), the farthest 127 - 32
    # = 95 >= 50 (masked); tile (3, 0) is 96 - 31 = 65 away: not visible;
    # tile (1, 0): nearest 1, farthest 63: the band's edge runs through it
    classes = lambda iq, ik: tuple(bool(c) for c in fa._causal_tile_classes(
        iq, ik, 32, 32, 50))
    assert classes(3, 1) == (False, True, True)
    assert classes(3, 0) == (False, False, False)
    assert classes(1, 0) == (False, True, True)
    assert classes(2, 2) == (False, True, True)         # the diagonal
    assert classes(2, 3) == (False, False, False)       # above it
    # window 64: tile (2, 1) is wholly inside the band (farthest 95 - 32)
    assert tuple(bool(c) for c in fa._causal_tile_classes(
        2, 1, 32, 32, 64)) == (True, False, True)


def test_a_window_needs_causal():
    q, k, v, _ = _inputs(groups=1)
    with pytest.raises(ValueError, match="causal"):
        fa.flash_attention(q, k, v, causal=False, window=8)
