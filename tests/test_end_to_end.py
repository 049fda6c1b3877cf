"""End-to-end distributed training on real (small) models.

Reference analogue: tests/python/integration/test_mnist_slp.py — a full
model trained through the framework must reach high accuracy; plus smoke
training for each model family.
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import kungfu_tpu.optimizers as kfopt
from kungfu_tpu.comm.mesh import flat_mesh
from kungfu_tpu.models import MnistMLP, MnistSLP, ResNet, bert_tiny
from kungfu_tpu.training import (broadcast_variables, build_train_step,
                                 build_train_step_with_state, init_opt_state,
                                 lane, replicate)

N = 8


def synthetic_digits(n=512, seed=0):
    """Linearly separable 'digits': class = argmax of 10 random projections."""
    rng = np.random.RandomState(seed)
    proj = rng.randn(64, 10).astype(np.float32)
    x = rng.randn(n, 8, 8, 1).astype(np.float32)
    y = (x.reshape(n, -1) @ proj).argmax(axis=1)
    return jnp.asarray(x), jnp.asarray(y)


def xent(logits, labels):
    return optax.softmax_cross_entropy_with_integer_labels(logits,
                                                           labels).mean()


@pytest.mark.parametrize("opt_name", ["sync", "sma", "pair", "ada"])
def test_mnist_mlp_all_optimizers(opt_name):
    model = MnistMLP(hidden=(32,), num_classes=10)
    x, y = synthetic_digits()
    params = model.init(jax.random.PRNGKey(0), x[:2])["params"]

    def loss_fn(p, batch):
        bx, by = batch
        return xent(model.apply({"params": p}, bx), by)

    base = optax.sgd(0.2)
    opt = {
        "sync": lambda: kfopt.synchronous_sgd(base),
        "sma": lambda: kfopt.synchronous_averaging(base, alpha=0.5),
        "pair": lambda: kfopt.pair_averaging(base, n=N),
        "ada": lambda: kfopt.adaptive_sgd(base, change_step=20, alpha=0.5),
    }[opt_name]()

    mesh = flat_mesh(n=N)
    sp = replicate(params, mesh)
    sp = broadcast_variables(sp, mesh)
    st = init_opt_state(opt, sp, mesh)
    step = build_train_step(loss_fn, opt, mesh)
    n_steps = 60 if opt_name in ("sync", "ada") else 150
    for i in range(n_steps):
        sp, st, loss = step(sp, st, (x, y))
    # evaluate lane-0 model
    p0 = lane(sp)
    logits = model.apply({"params": p0}, x)
    acc = (np.asarray(logits).argmax(axis=1) == np.asarray(y)).mean()
    assert acc > 0.8, f"{opt_name}: accuracy {acc}"


def test_resnet_with_batchnorm_state():
    model = ResNet(stage_sizes=[1, 1], num_classes=10, num_filters=8,
                   dtype=jnp.float32, small_inputs=True)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(N * 2, 8, 8, 3).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 10, size=N * 2))
    variables = model.init(jax.random.PRNGKey(0), x[:2])
    params, bstats = variables["params"], variables["batch_stats"]

    def loss_fn(p, mstate, batch):
        bx, by = batch
        logits, updated = model.apply({"params": p, "batch_stats": mstate},
                                      bx, train=True,
                                      mutable=["batch_stats"])
        return xent(logits, by), updated["batch_stats"]

    opt = kfopt.synchronous_sgd(optax.sgd(0.05))
    mesh = flat_mesh(n=N)
    sp = replicate(params, mesh)
    sms = replicate(bstats, mesh)
    st = init_opt_state(opt, sp, mesh)
    step = build_train_step_with_state(loss_fn, opt, mesh, donate=False)
    losses = []
    for _ in range(5):
        sp, st, sms, loss = step(sp, st, sms, (x, y))
        losses.append(float(np.asarray(loss)[0]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    # BN stats synced across lanes
    leaf = np.asarray(jax.tree_util.tree_leaves(sms)[0])
    np.testing.assert_allclose(leaf[0], leaf[-1], rtol=1e-5)


def test_bert_tiny_trains():
    model = bert_tiny(num_layers=1, hidden=32, num_heads=2, mlp_dim=64,
                      vocab_size=128, max_len=16, dtype=jnp.float32)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 128, size=(N * 2, 16)))
    params = model.init(jax.random.PRNGKey(0), tokens[:2])["params"]

    def loss_fn(p, batch):
        toks = batch
        logits = model.apply({"params": p}, toks)
        # trivial denoising objective: predict the input token
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, toks).mean()

    opt = kfopt.synchronous_sgd(optax.adam(1e-3))
    mesh = flat_mesh(n=N)
    sp = replicate(params, mesh)
    st = init_opt_state(opt, sp, mesh)
    step = build_train_step(loss_fn, opt, mesh)
    losses = []
    for _ in range(8):
        sp, st, loss = step(sp, st, tokens)
        losses.append(float(np.asarray(loss)[0]))
    assert losses[-1] < losses[0]


def test_noise_scale_on_real_model():
    model = MnistSLP()
    x, y = synthetic_digits(n=256)
    params = model.init(jax.random.PRNGKey(0), x[:2])["params"]

    def loss_fn(p, batch):
        bx, by = batch
        return xent(model.apply({"params": p}, bx), by)

    opt = kfopt.gradient_noise_scale(optax.sgd(0.1), batch_size=32)
    mesh = flat_mesh(n=N)
    sp = replicate(params, mesh)
    st = init_opt_state(opt, sp, mesh)
    step = build_train_step(loss_fn, opt, mesh)
    for _ in range(10):
        sp, st, loss = step(sp, st, (x, y))
    ns = np.asarray(st.noise_scale)
    assert np.isfinite(ns).all()


def test_resnet_accumulation_matches_sequential_microbatches():
    """With-state accumulation: grads average over microbatches, BN stats
    thread sequentially — exactly what running the microbatches by hand
    produces (single lane; with BatchNorm, microbatching is NOT equal to
    one big batch, because train-mode BN normalizes per microbatch)."""
    model = ResNet(stage_sizes=[1], num_classes=4, num_filters=8,
                   dtype=jnp.float32, small_inputs=True)
    mesh = flat_mesh(n=1)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(8, 8, 8, 3).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 4, size=8))
    variables = model.init(jax.random.PRNGKey(0), x[:2])
    params, bstats = variables["params"], variables["batch_stats"]

    def loss_fn(p, ms, batch):
        bx, by = batch
        logits, upd = model.apply({"params": p, "batch_stats": ms}, bx,
                                  train=True, mutable=["batch_stats"])
        return (optax.softmax_cross_entropy_with_integer_labels(
            logits, by).mean(), upd["batch_stats"])

    # oracle: two sequential microbatches by hand, mean grads, one update
    ms = bstats
    grads_sum = None
    for k in range(2):
        mb = (x[k * 4:(k + 1) * 4], y[k * 4:(k + 1) * 4])
        (_, ms), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, ms, mb)
        grads_sum = g if grads_sum is None else jax.tree_util.tree_map(
            jnp.add, grads_sum, g)
    base = optax.sgd(0.1)
    up, _ = base.update(jax.tree_util.tree_map(lambda t: t / 2, grads_sum),
                        base.init(params), params)
    ref_params = optax.apply_updates(params, up)

    opt = kfopt.synchronous_sgd(optax.sgd(0.1))
    sp = replicate(params, mesh)
    sms = replicate(bstats, mesh)
    st = init_opt_state(opt, sp, mesh)
    step = build_train_step_with_state(loss_fn, opt, mesh, donate=False,
                                       accum_steps=2)
    sp2, st2, sms2, loss2 = step(sp, st, sms, (x, y))

    from testutil import tree_allclose
    tree_allclose(jax.tree_util.tree_map(lambda t: np.asarray(t)[0], sp2),
                  ref_params)
    # BN stats equal the oracle's sequentially-threaded result
    tree_allclose(jax.tree_util.tree_map(lambda t: np.asarray(t)[0], sms2),
                  ms)
    assert np.isfinite(float(np.asarray(loss2)[0]))


@pytest.mark.parametrize("platform, options", [
    ("cpu", {}), ("tpu", {"xla_memory_scheduler": "list"})])
def test_the_accumulating_steps_compiler_options(platform, options):
    """Only a TPU mesh pins XLA's memory scheduler; the CPU backend does
    not know the option and must be handed none (every other test here
    compiles through it)."""
    import types

    from kungfu_tpu.training import _compiler_options
    device = types.SimpleNamespace(platform=platform)
    mesh = types.SimpleNamespace(devices=np.array([device], dtype=object))
    assert _compiler_options(mesh) == options
    assert _compiler_options(flat_mesh(jax.devices()[:1])) == {}
