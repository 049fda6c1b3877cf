"""BERT pretraining with SMA + gradient-noise-scale monitoring.

The reference's flagship monitored-training configuration: masked-LM
pretraining of a BERT encoder under synchronous model averaging, with the
gradient noise scale (An Empirical Model of Large-Batch Training)
estimated online from the same psum'd gradients — the reference's
MonitorGradientNoiseScaleOptimizer as a composable optax transform.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/bert_sma_gns.py
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
from kungfu_tpu.models import bert_tiny
from kungfu_tpu.training import (broadcast_variables, build_train_step,
                                 init_opt_state, replicate)

VOCAB, SEQ, MASK_ID = 512, 64, 0


def main():
    mesh = flat_mesh()
    n = int(np.prod(mesh.devices.shape))
    per_lane_batch = 4

    model = bert_tiny(vocab_size=VOCAB, max_len=SEQ,
                      dtype=jnp.bfloat16 if jax.devices()[0].platform == "tpu"
                      else jnp.float32)
    rng = np.random.RandomState(0)
    init_tokens = jnp.asarray(rng.randint(1, VOCAB, (2, SEQ)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), init_tokens, train=False)

    def loss_fn(p, batch):
        tokens, masked, is_masked = batch
        logits = model.apply(p, masked, train=True)
        nll = optax.softmax_cross_entropy_with_integer_labels(logits, tokens)
        return (nll * is_masked).sum() / jnp.maximum(is_masked.sum(), 1)

    # SMA keeps replicas loosely coupled (each applies its LOCAL gradient
    # plus a pull toward the average); the GNS monitor psums gradients for
    # its statistics only — apply="local" hands the un-averaged gradient
    # through so the replicas genuinely diverge between sync points
    opt = kfopt.synchronous_averaging(
        kfopt.gradient_noise_scale(optax.adam(1e-3),
                                   batch_size=per_lane_batch,
                                   apply="local"),
        alpha=0.1)
    sp = broadcast_variables(replicate(params, mesh), mesh)
    st = init_opt_state(opt, sp, mesh)
    step = build_train_step(loss_fn, opt, mesh, donate=False)

    def sample():
        tokens = rng.randint(1, VOCAB, (n * per_lane_batch, SEQ))
        is_masked = rng.rand(*tokens.shape) < 0.15
        masked = np.where(is_masked, MASK_ID, tokens)
        return (jnp.asarray(tokens, jnp.int32),
                jnp.asarray(masked, jnp.int32),
                jnp.asarray(is_masked, jnp.float32))

    for i in range(10):
        sp, st, loss = step(sp, st, sample())
        ns = float(np.asarray(st.noise_scale)[0])
        print(f"step {i}: mlm_loss={float(np.asarray(loss)[0]):.4f} "
              f"noise_scale={ns:.1f}")


if __name__ == "__main__":
    main()
