"""ZeRO-sharded data parallelism: ZeRO-3 (fsdp) vs ZeRO-1 side by side.

Both shard optimizer state 1/n per device; ZeRO-3 also shards the
parameters themselves (all-gather before compute, reduce-scatter after).
Extensions beyond the reference framework's pure-DP envelope
(SURVEY.md §2.4).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/fsdp_zero.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh

from kungfu_tpu.parallel import make_fsdp_step, make_zero1_step


def main():
    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("fsdp",))
    n = len(devices)

    rng = np.random.RandomState(0)
    params = {"w1": jnp.asarray(rng.randn(64, 128).astype(np.float32) * 0.1),
              "w2": jnp.asarray(rng.randn(128, 8).astype(np.float32) * 0.1)}
    x = jnp.asarray(rng.randn(8 * n, 64).astype(np.float32))
    y = jnp.asarray(rng.randn(8 * n, 8).astype(np.float32))

    def loss_fn(p, batch):
        bx, by = batch
        h = jax.nn.relu(bx @ p["w1"])
        return jnp.mean((h @ p["w2"] - by) ** 2)

    for name, maker in (("ZeRO-3 (fsdp)", make_fsdp_step),
                        ("ZeRO-1", make_zero1_step)):
        init, make_step = maker(loss_fn, optax.adam(1e-2), mesh)
        state, opt_state, meta = init(params)
        step = make_step(meta)
        losses = []
        for _ in range(40):
            state, opt_state, loss = step(state, opt_state, (x, y))
            losses.append(float(np.asarray(loss)))
        layout = ("replicated" if state.sharding.is_fully_replicated
                  else f"sharded {n}-way")
        print(f"{name:14s} loss {losses[0]:.4f} -> {losses[-1]:.4f}  "
              f"(params {layout}, opt state sharded {n}-way)")


if __name__ == "__main__":
    main()
