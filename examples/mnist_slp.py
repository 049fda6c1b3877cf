"""Synchronous-SGD MNIST softmax classifier — the reference's minimum
end-to-end example (reference: examples/tf2_mnist_gradient_tape.py).

Run on all local devices (virtual CPU mesh works too):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/mnist_slp.py

Each mesh lane trains a model replica on its shard of the global batch;
`synchronous_sgd` allreduces gradients inside the compiled step.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import kungfu_tpu.optimizers as kfopt
from kungfu_tpu.comm.mesh import flat_mesh
from kungfu_tpu.training import (broadcast_variables, build_train_step,
                                 init_opt_state, lane, replicate)


def load_mnist():
    """Real MNIST when MNIST_DIR points at the idx files, else the
    deterministic synthetic stand-in (kungfu_tpu.data.mnist)."""
    from kungfu_tpu.data import mnist
    (x, y), _ = mnist(os.environ.get("MNIST_DIR") or None)
    return x.reshape(len(x), -1), y


def main():
    mesh = flat_mesh()
    n_lanes = int(np.prod(mesh.devices.shape))
    global_batch = 64 * n_lanes
    print(f"training on {n_lanes} lanes, global batch {global_batch}")

    params = {"w": jnp.zeros((28 * 28, 10)), "b": jnp.zeros((10,))}

    def loss_fn(p, batch):
        x, y = batch
        logits = x @ p["w"] + p["b"]
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    opt = kfopt.synchronous_sgd(optax.sgd(0.1))
    sp = replicate(params, mesh)
    sp = broadcast_variables(sp, mesh)   # rank-0 init everywhere
    st = init_opt_state(opt, sp, mesh)
    step = build_train_step(loss_fn, opt, mesh)

    x, y = load_mnist()
    for epoch in range(3):
        perm = np.random.RandomState(epoch).permutation(len(x))
        for i in range(0, len(x) - global_batch + 1, global_batch):
            idx = perm[i:i + global_batch]
            sp, st, loss = step(sp, st, (jnp.asarray(x[idx]),
                                         jnp.asarray(y[idx])))
        print(f"epoch {epoch}: loss {float(np.asarray(loss)[0]):.4f}")

    final = lane(sp)   # replicas are identical under sync SGD
    acc = (x @ final["w"] + final["b"]).argmax(axis=1)
    print(f"train accuracy: {(acc == y).mean():.3f}")


if __name__ == "__main__":
    main()
