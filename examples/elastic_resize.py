"""Elastic training with a step-based resize schedule and checkpointing.

Reference flow: kungfu-run -w + config server + KungfuStepBasedSchedule
(reference: tests/python/integration/test_tensorflow_resize.py,
ops/cpu/elastic.cpp step-schedule op).  Here the controller process resizes
the mesh at scheduled steps; replicas and optimizer state survive, and
compiled steps are cached per size.  Midway the run checkpoints to disk
and a FRESH trainer resumes at a different cluster size — the elastic
story extended across restarts (beyond the reference, which keeps no
disk checkpoints).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/elastic_resize.py
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import kungfu_tpu.optimizers as kfopt
from kungfu_tpu.checkpoint import Checkpointer
from kungfu_tpu.elastic import ElasticTrainer, StepSchedule
from kungfu_tpu.elastic.dataset import ElasticDataShard


def main():
    # "np:steps,np:steps" exactly like KungfuStepBasedSchedule
    schedule = StepSchedule.parse("2:5,4:5,8:5,4:5")

    params = {"w": jnp.zeros((16, 4))}

    def loss_fn(p, batch):
        x, y = batch
        return ((x @ p["w"] - y) ** 2).mean()

    tr = ElasticTrainer(
        loss_fn,
        optimizer_factory=lambda n: kfopt.synchronous_sgd(optax.sgd(0.05)),
        init_params=params,
        init_size=schedule.size_at(0),
    )

    rng = np.random.RandomState(0)
    xs = rng.randn(4096, 16).astype(np.float32)
    ys = rng.randn(4096, 4).astype(np.float32)
    shard = ElasticDataShard(len(xs))

    per_lane_batch = 16
    half = schedule.total_steps() // 2
    with tempfile.TemporaryDirectory(prefix="kft_ckpt_") as ckpt_dir, \
            Checkpointer(ckpt_dir) as ck:
        for step_i in range(half):
            want = schedule.size_at(step_i)
            if want != tr.n:
                print(f"step {step_i}: resize {tr.n} -> {want}")
                tr.resize(want)
            idx = shard.batch_indices(tr.trained_samples,
                                      per_lane_batch * tr.n)
            loss = tr.step((jnp.asarray(xs[idx]), jnp.asarray(ys[idx])))
            if step_i % 5 == 0:
                print(f"step {step_i:3d} lanes={tr.n} loss={loss:.4f} "
                      f"samples={tr.trained_samples}")
        tr.save_checkpoint(ck)
        ck.wait()
        print(f"checkpointed at step {tr.step_count} "
              f"({tr.trained_samples} samples)")

        # simulate a restart: a fresh trainer at a DIFFERENT size resumes
        tr2 = ElasticTrainer(
            loss_fn,
            optimizer_factory=lambda n: kfopt.synchronous_sgd(
                optax.sgd(0.05)),
            init_params=params,
            init_size=schedule.size_at(half),
        )
        resumed_at = tr2.restore_checkpoint(ck)
        print(f"resumed step {resumed_at} at lanes={tr2.n}")

    for step_i in range(half, schedule.total_steps()):
        want = schedule.size_at(step_i)
        if want != tr2.n:
            print(f"step {step_i}: resize {tr2.n} -> {want}")
            tr2.resize(want)
        idx = shard.batch_indices(tr2.trained_samples,
                                  per_lane_batch * tr2.n)
        loss = tr2.step((jnp.asarray(xs[idx]), jnp.asarray(ys[idx])))
        if step_i % 5 == 0:
            print(f"step {step_i:3d} lanes={tr2.n} loss={loss:.4f} "
                  f"samples={tr2.trained_samples}")
    print(f"done: {tr2.trained_samples} samples, final lanes={tr2.n}")


if __name__ == "__main__":
    main()
