"""The `phi4flash` family through the program: `models/gpt.py` with the
SambaY layers of `models/sambay.py` (mamba layers on the selective-scan
kernels, differential attention windowed and full on one flash call a
layer with values twice the width of the keys, a gated memory unit and
cross-attention reading the memory and the shared KV the stack carries),
LayerNorm with a bias, the chunked loss over the head tied to the
embedding, under `training.build_train_step`."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from perf import program
from perf.reference import phi4flash as ref

KIND = {"mamba": "mamba", "swa": "diff", "full": "diff", "gmu": "gmu",
        "cross": "cross"}


def model_config(config: dict, traffic: dict):
    """The program's configuration of a file's sizes."""
    import dataclasses
    from kungfu_tpu.models.gpt import GPTConfig
    lacks = {"first_layer", "norm"} - {f.name for f in dataclasses.fields(GPTConfig)}
    if lacks:       # a program from before the family: say so and leave
        raise SystemExit(f"models/gpt.py's GPTConfig has no "
                         f"{sorted(lacks)}: this program cannot run the "
                         f"phi4flash family")
    from kungfu_tpu.models import sambay
    s = ref.sizes(config)
    cfg = GPTConfig(
        vocab_size=s["V"], d_model=s["D"], n_heads=s["H"],
        n_kv_heads=s["Hkv"], d_head=s["Dh"], n_layers=s["L"], d_ff=s["F"],
        max_seq=traffic["seq_len"], dtype=jnp.bfloat16,
        rope=(False,) * s["L"], mlp="swiglu",
        norm="layer", norm_eps=s["eps"],
        window=tuple(s["window"] if k == "swa" else None
                     for k in s["kinds"]),
        operator=tuple(KIND[k] for k in s["kinds"]),
        first_layer=config["first_layer"])
    if ((s["N"], s["K"], s["E"], s["R"], s["memory"], s["shared"])
            != (sambay.D_STATE, sambay.D_CONV, sambay.EXPAND * s["D"],
                sambay.dt_rank(cfg), sambay.memory_layer(cfg),
                sambay.kv_layer(cfg))):
        raise ValueError("models/sambay.py's mamba layer has Mamba-1's sizes "
                         "and reads the memory and the KV of the last mamba "
                         "and diff layers before the readers; this "
                         "configuration states otherwise")
    return cfg


def build(config: dict, traffic: dict, mesh) -> program.Job:
    cfg = model_config(config, traffic)     # first: it names what is missing
    from kungfu_tpu.models.gpt import forward_features
    from kungfu_tpu.ops.chunked_ce import chunked_cross_entropy
    from kungfu_tpu.training import build_train_step, init_opt_state

    remat, chunk = traffic.get("remat", ""), traffic["ce_chunk"]

    def loss_fn(p, batch):
        tokens, targets = batch
        feats = forward_features(p, tokens, cfg, attn="flash", remat=remat)
        # the head is the embedding, [V, D] read as [D, V]
        return chunked_cross_entropy(feats, p["wte"].astype(cfg.dtype).T,
                                     targets, chunk).mean()

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
