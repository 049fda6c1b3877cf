"""The `ouro` family through the program: `models/looped.py` (the layer
stack of `models/gpt.py` run `total_ut_steps` times under one set of
weights, an exit gate, the exit distribution's loss over four chunked heads)
with the flash kernels, under `training.build_train_step`, wired as the
`gpt` adapter wires the plain decoder."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from perf import program
from perf.reference import ouro as ref


def build(config: dict, traffic: dict, mesh) -> program.Job:
    from kungfu_tpu.models import looped
    from kungfu_tpu.models.gpt import GPTConfig
    from kungfu_tpu.training import build_train_step, init_opt_state

    s = ref.sizes(config)
    if s["Dh"] * s["H"] != s["D"]:
        raise ValueError("models/gpt.py takes head_dim = hidden / heads")
    if config.get("use_sliding_window"):
        raise ValueError("models/gpt.py has no window mask")
    cfg = GPTConfig(vocab_size=s["V"], d_model=s["D"], n_heads=s["H"],
                    n_layers=s["L"], d_ff=s["F"], max_seq=traffic["seq_len"],
                    dtype=jnp.bfloat16, n_kv_heads=s["Hkv"], rope=True,
                    mlp="swiglu", norm_eps=s["eps"],
                    rope_theta=float(s["theta"]), out_norms=True,
                    n_rounds=s["R"])
    remat, chunk = traffic.get("remat", ""), traffic["ce_chunk"]

    def loss_fn(p, batch):
        tokens, targets = batch
        return looped.loss_fn(p, tokens, targets, cfg, beta=s["beta"],
                              ce_chunk=chunk, attn="flash", remat=remat)

    opt = program.optimizer(traffic["optimizer"])
    train = build_train_step(loss_fn, opt, mesh, donate=True,
                             accum_steps=traffic["accum_steps"],
                             compute_dtype=cfg.dtype)
    make = program.stacked(lambda key: ref.init_params(key, config), mesh)

    def init_state(key):
        params = make(key)
        return params, init_opt_state(opt, params, mesh)

    def step(state, batch):
        params, opt_state, loss = train(state[0], state[1], batch)
        return (params, opt_state), loss

    return program.Job(
        step=step, lower=lambda st, b: train.lower(st[0], st[1], b),
        init_state=init_state,
        place=lambda x: jax.device_put(x, program.stack_sharding(mesh)),
        units_per_step=traffic["batch"] * traffic["seq_len"],
        optimizer=traffic["optimizer"], ref_family=ref, config=config)
