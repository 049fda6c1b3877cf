"""The `resnet` family through the program: `models.ResNet` in bf16 under
`training.build_train_step_with_state`, as `bench.py` builds it, fed uint8
images that the loss casts and scales on the device."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from perf import program
from perf.reference import resnet as ref


def build(config: dict, traffic: dict, mesh) -> program.Job:
    from kungfu_tpu.models.resnet import ResNet
    from kungfu_tpu.training import (build_train_step_with_state,
                                     init_opt_state)

    model = ResNet(stage_sizes=config["stage_sizes"],
                   num_classes=config["num_classes"],
                   num_filters=config["num_filters"], dtype=jnp.bfloat16)

    def loss_fn(p, mstate, batch):
        images, labels = batch
        logits, updated = model.apply(
            {"params": p, "batch_stats": mstate}, ref.scale_images(images),
            train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        return loss, updated["batch_stats"]

    opt = program.optimizer(traffic["optimizer"])
    train = build_train_step_with_state(loss_fn, opt, mesh, donate=False)
    make = program.stacked(lambda key: ref.init_params(key, config), mesh)
    make_stats = program.stacked(lambda key: ref.init_model_state(config),
                                 mesh)

    def init_state(key):
        params = make(key)
        return params, init_opt_state(opt, params, mesh), make_stats(key)

    def step(state, batch):
        params, opt_state, mstate, loss = train(*state, batch)
        return (params, opt_state, mstate), loss

    return program.Job(
        step=step, lower=lambda st, b: train.lower(*st, b),
        init_state=init_state,
        place=lambda x: jax.device_put(x, program.stack_sharding(mesh)),
        units_per_step=traffic["batch"], optimizer=traffic["optimizer"],
        ref_family=ref, config=config)
