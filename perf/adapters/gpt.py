"""The `gpt` family through the program: `models/gpt.py` with the flash
kernels and the chunked loss, as `benchmarks/gpt.py` wires them, under
`training.build_train_step`."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from perf import program
from perf.reference import gpt as ref

# constants of models/gpt.py that a configuration file also states
PROGRAM_RMS_EPS, PROGRAM_ROPE_THETA = 1e-5, 10000.0


def build(config: dict, traffic: dict, mesh) -> program.Job:
    from kungfu_tpu.models.gpt import GPTConfig, forward_features
    from kungfu_tpu.ops.chunked_ce import chunked_cross_entropy
    from kungfu_tpu.training import build_train_step, init_opt_state

    s = ref.sizes(config)
    if (s["eps"], float(s["theta"])) != (PROGRAM_RMS_EPS, PROGRAM_ROPE_THETA):
        raise ValueError("models/gpt.py fixes rms_norm eps 1e-5 and RoPE "
                         "base 10000; this configuration states "
                         f"{s['eps']} and {s['theta']}")
    if s["Dh"] * s["H"] != s["D"]:
        raise ValueError("models/gpt.py takes head_dim = hidden / heads")
    if config.get("sliding_window") and (traffic["seq_len"]
                                         > config["sliding_window"]):
        raise ValueError("models/gpt.py has no window mask: sequences must "
                         "not be longer than sliding_window")
    cfg = GPTConfig(vocab_size=s["V"], d_model=s["D"], n_heads=s["H"],
                    n_layers=s["L"], d_ff=s["F"], max_seq=traffic["seq_len"],
                    dtype=jnp.bfloat16, n_kv_heads=s["Hkv"], rope=True,
                    mlp="swiglu")
    remat, chunk = traffic.get("remat", ""), traffic["ce_chunk"]

    def loss_fn(p, batch):
        tokens, targets = batch
        feats = forward_features(p, tokens, cfg, attn="flash", remat=remat)
        head = p["lm_head"].astype(cfg.dtype)
        return chunked_cross_entropy(feats, head, targets, chunk).mean()

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
