"""Pipeline-parallel GPT training (dp x pp, optionally x tp).

The layer stack is sharded across pipeline stages; microbatches flow
through a GPipe schedule compiled as one lax.scan (ppermute stage
transfer, AD-generated backward pipeline).  ``--virtual-stages v``
switches to the Megatron-style interleaved schedule (each rank holds v
layer chunks; compute bubble 1 + (S-1)/(v*M) instead of 1 + (S-1)/M).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/gpt_pipeline.py [--virtual-stages 2]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from kungfu_tpu.models.gpt import GPTConfig
from kungfu_tpu.parallel import pipeline as PP


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--virtual-stages", type=int, default=1)
    args = ap.parse_args()
    v = args.virtual_stages
    devices = jax.devices()
    assert len(devices) >= 8, "run with an 8-device mesh (see module doc)"
    cfg = GPTConfig(vocab_size=512, d_model=128, n_heads=8, n_layers=8,
                    d_ff=512, max_seq=256,
                    dtype=jnp.bfloat16 if devices[0].platform == "tpu"
                    else jnp.float32)
    # 2-way data parallel x 2 pipeline stages x 2-way tensor parallel
    mesh = PP.mesh_dp_pp_tp(2, 2, 2, devices)
    opt = optax.adamw(3e-4)
    params, state = PP.init_gpt_pp(cfg, opt, mesh, virtual_stages=v)
    step = PP.make_gpt_pp_train_step(cfg, opt, mesh, n_micro=4,
                                     virtual_stages=v)

    rng = np.random.RandomState(0)
    batch, seq = 8, 64
    for i in range(10):
        toks = rng.randint(0, cfg.vocab_size, (batch, seq + 1))
        tokens = jnp.asarray(toks[:, :-1], jnp.int32)
        targets = jnp.asarray(toks[:, 1:], jnp.int32)
        params, state, loss = step(params, state, tokens, targets)
        print(f"step {i}: loss={float(loss):.4f}")


if __name__ == "__main__":
    main()
