"""3D-parallel GPT training: dp x sp x tp in one compiled step.

Composes the framework's parallel axes — data parallelism (the reference
framework's envelope), ring-attention sequence parallelism, and
Megatron-style tensor parallelism with a vocab-sharded parallel
cross-entropy — over an 8-device mesh.

Run anywhere:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/gpt_3d_parallel.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from kungfu_tpu.models.gpt import GPTConfig
from kungfu_tpu.parallel import threed as T3


def main():
    devices = jax.devices()
    assert len(devices) >= 8, "run with an 8-device mesh (see module doc)"

    # the LLaMA-style configuration: RoPE + grouped-query attention +
    # SwiGLU, all composable with the 3D mesh
    cfg = GPTConfig(vocab_size=512, d_model=128, n_heads=8, n_layers=4,
                    d_ff=512, max_seq=256, rope=True, n_kv_heads=4,
                    mlp="swiglu",
                    dtype=jnp.bfloat16 if devices[0].platform == "tpu"
                    else jnp.float32)
    mesh = T3.mesh_3d(dp=2, sp=2, tp=2, devices=devices)
    opt = optax.adamw(3e-4)
    params, state = T3.init_gpt(cfg, opt, mesh)
    step = T3.make_gpt_train_step(cfg, opt, mesh, attn="ring")

    rng = np.random.RandomState(0)
    batch, seq = 8, 64  # batch sharded over dp, sequence over sp

    def sample():
        toks = rng.randint(0, cfg.vocab_size, (batch, seq + 1))
        return (jnp.asarray(toks[:, :-1], jnp.int32),
                jnp.asarray(toks[:, 1:], jnp.int32))

    for i in range(10):
        tokens, targets = sample()
        params, state, loss = step(params, state, tokens, targets)
        print(f"step {i}: loss={float(loss):.4f}")


if __name__ == "__main__":
    main()
