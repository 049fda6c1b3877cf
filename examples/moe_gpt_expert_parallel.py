"""MoE-GPT under expert parallelism (dp x ep).

Every 2nd transformer block routes tokens to switch-MoE experts sharded
over the ep axis (all_to_all dispatch, static capacity, load-balancing
auxiliary loss).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/moe_gpt_expert_parallel.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from kungfu_tpu.models.gpt import GPTConfig
from kungfu_tpu.parallel import moe_gpt as MG


def main():
    devices = jax.devices()
    assert len(devices) >= 8, "run with an 8-device mesh (see module doc)"
    cfg = MG.MoEGPTConfig(
        gpt=GPTConfig(vocab_size=512, d_model=128, n_heads=8, n_layers=4,
                      d_ff=512, max_seq=256,
                      dtype=jnp.bfloat16 if devices[0].platform == "tpu"
                      else jnp.float32),
        n_experts=8, expert_every=2, capacity_factor=1.5)
    mesh = MG.mesh_dp_ep(2, 4, devices)
    opt = optax.adamw(3e-4)
    params, state = MG.init_moe_gpt(cfg, opt, mesh)
    step = MG.make_train_step(cfg, opt, mesh)

    rng = np.random.RandomState(0)
    batch, seq = 16, 64  # batch sharded over dp x ep = 8 lanes
    for i in range(10):
        toks = rng.randint(0, cfg.gpt.vocab_size, (batch, seq + 1))
        tokens = jnp.asarray(toks[:, :-1], jnp.int32)
        targets = jnp.asarray(toks[:, 1:], jnp.int32)
        params, state, loss = step(params, state, tokens, targets)
        print(f"step {i}: loss={float(loss):.4f}")


if __name__ == "__main__":
    main()
