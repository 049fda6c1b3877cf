"""AD-PSGD pair averaging (reference: PairAveragingOptimizer,
optimizers/async_sgd.py) — each lane trains independently and mixes
parameters with a scheduled partner via `ppermute` each step.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/pair_averaging.py
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
from kungfu_tpu.training import (build_train_step, init_opt_state, lane_mean,
                                 replicate)


def main():
    mesh = flat_mesh()
    n = int(np.prod(mesh.devices.shape))

    params = {"w": jnp.zeros((8, 1))}
    rng = np.random.RandomState(0)
    w_true = rng.randn(8, 1).astype(np.float32)

    def loss_fn(p, batch):
        x, y = batch
        return ((x @ p["w"] - y) ** 2).mean()

    opt = kfopt.pair_averaging(optax.sgd(0.05), n=n)
    sp = replicate(params, mesh)
    st = init_opt_state(opt, sp, mesh)
    step = build_train_step(loss_fn, opt, mesh)

    for i in range(200):
        # every lane sees a DIFFERENT batch — gossip keeps them converging
        x = rng.randn(16 * n, 8).astype(np.float32)
        y = x @ w_true + 0.01 * rng.randn(16 * n, 1).astype(np.float32)
        sp, st, loss = step(sp, st, (jnp.asarray(x), jnp.asarray(y)))
        if i % 50 == 0:
            print(f"step {i:3d} loss={float(np.asarray(loss)[0]):.5f}")

    err = np.abs(lane_mean(sp)["w"] - w_true).max()
    print(f"max |w - w_true| over averaged replicas: {err:.4f}")


if __name__ == "__main__":
    main()
