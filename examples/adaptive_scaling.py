"""GNS-driven autoscaling: the adaptive loop closed end to end.

The gradient-noise-scale monitor estimates the critical batch size
while training; GNSScalingPolicy proposes cluster sizes so the global
batch tracks it; ElasticTrainer applies them as live resizes (state
re-synced, trained-samples preserved).  The reference monitors GNS
(MonitorGradientNoiseScaleOptimizer) and resizes on operator/schedule
input; this closes the loop between the two.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/adaptive_scaling.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import kungfu_tpu.optimizers as kfopt
from kungfu_tpu.elastic.policy import GNSScalingPolicy, PolicyRunner
from kungfu_tpu.elastic.trainer import ElasticTrainer

PER_LANE = 8   # small per-lane batch: the critical batch (GNS) exceeds
               # it by several x on this noisy task, so scaling out pays


def main():
    rng = np.random.RandomState(0)
    W = jnp.asarray(rng.randn(32, 8), jnp.float32)

    def loss(p, batch):
        bx, by = batch
        return jnp.mean((bx @ p["w"] - by) ** 2)

    def factory(n):
        # batch_size is the monitor's B_small = the PER-LANE batch; it
        # derives B_big = n * B_small from the mesh itself
        return kfopt.gradient_noise_scale(
            kfopt.synchronous_sgd(optax.sgd(0.05)),
            batch_size=PER_LANE)

    n0 = min(2, len(jax.devices()))
    tr = ElasticTrainer(loss, factory,
                        init_params={"w": jnp.zeros((32, 8))},
                        init_size=n0)

    def batch_fn(trainer):
        n = trainer.n * PER_LANE
        bx = jnp.asarray(rng.randn(n, 32), jnp.float32)
        noise = 4.0 * jnp.asarray(rng.randn(n, 8), jnp.float32)
        return bx, bx @ W + noise

    pol = GNSScalingPolicy(PER_LANE, min_size=1,
                           max_size=len(jax.devices()),
                           check_every=5, warmup_steps=10,
                           cooldown_steps=15, deadband=1.3)
    runner = PolicyRunner([pol], tr, epoch_size=PER_LANE * n0 * 40,
                          epochs=1)
    losses = runner.run(batch_fn, steps_per_epoch=40)
    print(f"final loss {losses[-1]:.4f} over {len(losses)} steps")
    for step, gns, want in pol.history:
        act = f"-> resize to {want}" if want else ""
        print(f"  step {step:3d}  gns {gns:8.1f}  "
              f"(critical batch est.) {act}")
    print(f"final cluster size: {tr.n} lanes "
          f"(started at {n0}); trained_samples={tr.trained_samples}")


if __name__ == "__main__":
    main()
