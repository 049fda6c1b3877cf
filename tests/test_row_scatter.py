"""The expert layer's scatter-add into the tokens (ops/row_scatter.py),
interpreted on the CPU: the kernel against `.at[].add` bit for bit, at the
edges of a block's live rows, and the layer around it (parallel/moe.py)
against the same layer whose rows go back through NumPy's `np.add.at`."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kungfu_tpu.ops import flash_attention, row_scatter
from kungfu_tpu.parallel import moe


def _block(n, D, block, n_live, seed=0, first=None):
    """A carry [n, D], a block of `n_live` distinct rows rising then
    padding that names row 0, and the block's rows to add."""
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    carry = jax.random.normal(k[0], (n, D))
    live = np.sort(np.asarray(jax.random.permutation(k[1], n))[:n_live])
    if first is not None and n_live:
        live = np.sort(np.concatenate([[first], live[live != first]]))[
            :n_live]
    idx = np.zeros(block, np.int32)
    idx[:n_live] = live
    return carry, jnp.asarray(idx), jax.random.normal(k[2], (block, D))


def _added(carry, idx, n_live, upd):
    """Through the kernel: into the carry's layout and back."""
    n, D = carry.shape
    shape = row_scatter.carry_shape(n, D)
    tiled = np.zeros(shape, np.float32)
    tiled[:, :D // shape[2]] = np.asarray(carry).reshape(n, -1, shape[2])
    out = jax.jit(row_scatter.scatter_add_rows)(
        jnp.asarray(tiled), idx, jnp.int32(n_live), upd)
    return row_scatter.rows_of(out, D, out.dtype)


@pytest.mark.parametrize("D", [128, 256])
@pytest.mark.parametrize("block", [8, 16])
@pytest.mark.parametrize("n_live", ["none", "one", "some", "all"])
def test_the_kernel_is_at_add_bit_for_bit(D, block, n_live):
    n_live = {"none": 0, "one": 1, "some": block // 2 + 1, "all": block}[
        n_live]
    carry, idx, upd = _block(40, D, block, n_live)
    got = _added(carry, idx, n_live, upd)
    want = carry.at[idx[:n_live]].add(upd[:n_live])
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("block, n_live", [(8, 3), (16, 16), (16, 9)])
def test_padding_that_names_a_live_row_adds_nothing_to_it(block, n_live):
    # row 0 is live and every padding row names it too
    carry, idx, upd = _block(40, 128, block, n_live, seed=1, first=0)
    assert int(idx[0]) == 0 and (np.asarray(idx[n_live:]) == 0).all()
    got = _added(carry, idx, n_live, upd)
    want = carry.at[idx[:n_live]].add(upd[:n_live])
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(got[0]), np.asarray(carry[0] + upd[0]))


@pytest.mark.parametrize("D, shape", [
    # whole tiles, a width that ends inside its last tile, one not of lanes
    (2048, (5, 16, 128)), (2560, (5, 24, 128)), (384, (5, 8, 128)),
    (24, (5, 1, 24))])
def test_the_carry_holds_each_row_as_tiles_of_its_own(D, shape):
    assert row_scatter.carry_shape(5, D) == shape
    carry = jax.random.normal(jax.random.PRNGKey(2), (5, D))
    tiled = jnp.zeros(shape).at[:, :D // shape[2]].set(
        carry.reshape(5, -1, shape[2]))
    np.testing.assert_array_equal(
        row_scatter.rows_of(tiled, D, jnp.float32), carry)


def test_the_lane_rows_past_the_width_are_left_as_they_were():
    carry, idx, upd = _block(5, 384, 8, 3, seed=2)
    got = jax.jit(row_scatter.scatter_add_rows)(
        jnp.ones(row_scatter.carry_shape(5, 384)), idx, jnp.int32(3), upd)
    assert (np.asarray(got)[:, 3:] == 1).all()
    np.testing.assert_array_equal(row_scatter.rows_of(got, 384, got.dtype),
                                  jnp.ones((5, 384)).at[idx[:3]].add(upd[:3]))


def test_a_width_of_no_whole_lanes_is_refused_on_the_chip(monkeypatch):
    monkeypatch.setattr(flash_attention, "_auto_interpret", lambda: False)
    carry, idx, upd = _block(40, 24, 8, 3)
    with pytest.raises(ValueError, match="moe_scatter_add.*multiple of 128"):
        row_scatter.scatter_add_rows(
            jnp.zeros(row_scatter.carry_shape(40, 24)), idx, jnp.int32(3),
            upd)


def _numpy_adds(carry, idx, n_live, upd):
    """The reference: the rows added by `np.add.at`, on the host."""
    def add(c, i, n, u):
        c = np.array(c)
        R, L = u.shape[1] // c.shape[2], c.shape[2]
        view = c[:, :R]
        np.add.at(view, i[:int(n)], u[:int(n)].reshape(-1, R, L))
        return c
    return jax.pure_callback(add, jax.ShapeDtypeStruct(carry.shape,
                                                       carry.dtype),
                             carry, idx, n_live, upd)


@pytest.mark.parametrize("act", ["reglu", "swiglu"])
def test_the_layer_and_its_gradients_are_the_numpy_adds_bit_for_bit(
        act, monkeypatch):
    n, k, E, D, F, held, block = 48, 3, 8, 128, 16, (2, 4), 16
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    x = jax.random.normal(keys[0], (n, D))
    ids = jax.lax.top_k(jax.random.normal(keys[1], (n, E)), k)[1].astype(
        jnp.int32)
    weights = jax.random.uniform(keys[2], (n, k), minval=0.1)
    wi = jax.random.normal(keys[3], (held[1], D, 2 * F)) / np.sqrt(D)
    wm = jax.random.normal(keys[4], (held[1], F, D)) / np.sqrt(F)
    c = jax.random.normal(keys[5], (n, D))

    def both():
        layer = lambda *a: moe.expert_ffn(a[0], ids, *a[1:], held, block,
                                          act=act)
        return jax.jit(lambda *a: (layer(*a), jax.grad(
            lambda *a: jnp.sum(c * layer(*a)), (0, 1, 2, 3))(*a)))(
                x, weights, wi, wm)
    got = both()
    monkeypatch.setattr(moe, "scatter_add_rows", _numpy_adds)
    want = both()
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.array_equal(np.asarray(g), np.asarray(w))
    assert float(jnp.abs(got[0]).sum()) > 0
