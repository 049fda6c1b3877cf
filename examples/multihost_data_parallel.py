"""Multi-host data-parallel training: one jax runtime spanning processes.

The real TPU-pod deployment shape: `kft-run` spawns one worker per host,
each calls `kungfu_tpu.init_distributed()` (coordinator derived from the
shared peer list), and a single global mesh spans every process's chips —
collectives ride ICI/DCN.  Here each process contributes virtual CPU
devices so the same program runs anywhere:

    XLA_FLAGS=--xla_force_host_platform_device_count=2 JAX_PLATFORMS=cpu \\
        python -m kungfu_tpu.launcher -np 2 -- \\
        python examples/multihost_data_parallel.py

Each process feeds only its LOCAL shard of the global batch
(`jax.make_array_from_process_local_data`); the compiled step is identical
on every process and the mean loss/parameters stay bit-identical.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.experimental import multihost_utils

import kungfu_tpu as kft
import kungfu_tpu.optimizers as kfopt
from kungfu_tpu.comm.mesh import flat_mesh, peer_sharding
from kungfu_tpu.training import (broadcast_variables, build_train_step,
                                 init_opt_state, replicate)


def main():
    distributed = kft.init_distributed()
    mesh = flat_mesh()  # all devices across all processes
    n_dev = int(np.prod(mesh.devices.shape))
    rank, nproc = jax.process_index(), jax.process_count()
    per_proc = n_dev // nproc
    print(f"rank {rank}/{nproc}: {per_proc} local of {n_dev} global devices"
          f" (distributed={distributed})")

    rng = np.random.RandomState(0)  # identical on every process
    w_true = rng.randn(16, 4).astype(np.float32)
    params = {"w": jnp.zeros((16, 4)), "b": jnp.zeros((4,))}

    def loss_fn(p, batch):
        bx, by = batch
        return jnp.mean((bx @ p["w"] + p["b"] - by) ** 2)

    opt = kfopt.synchronous_sgd(optax.sgd(0.2))
    sp = broadcast_variables(replicate(params, mesh), mesh)
    st = init_opt_state(opt, sp, mesh)
    step = build_train_step(loss_fn, opt, mesh)

    data_sharding = peer_sharding(mesh)
    per_dev_batch = 32
    data_rng = np.random.RandomState(100 + rank)  # local data differs

    for i in range(100):
        # this process's slice of the global batch only
        bx = data_rng.randn(per_proc * per_dev_batch, 16).astype(np.float32)
        by = bx @ w_true + 0.01 * data_rng.randn(
            per_proc * per_dev_batch, 4).astype(np.float32)
        gx = jax.make_array_from_process_local_data(data_sharding, bx)
        gy = jax.make_array_from_process_local_data(data_sharding, by)
        sp, st, loss = step(sp, st, (gx, gy))
        if i % 25 == 0:
            lv = float(np.asarray(
                multihost_utils.process_allgather(
                    loss[:1], tiled=True))[0])
            print(f"rank {rank} step {i}: loss {lv:.5f}")

    final = float(np.asarray(
        multihost_utils.process_allgather(
            loss[:1], tiled=True))[0])
    err = float(np.abs(np.asarray(sp["w"].addressable_data(0)) -
                       w_true).max())
    print(f"rank {rank}: final loss {final:.5f}, |w - w_true| {err:.4f}")
    assert err < 0.05, err
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
