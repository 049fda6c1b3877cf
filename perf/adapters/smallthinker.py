"""The `smallthinker` family through the program: `models/gpt.py` with a
head size of its own, layers of two kinds (full attention without
positions, windowed attention with RoPE) and a routed feed-forward without
dropped tokens over the experts held here (`parallel/moe.py`), the flash
kernels with their window, the chunked loss, under
`training.build_train_step`, wired as the `gpt` adapter wires the plain
decoder."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from perf import program
from perf.reference import smallthinker as ref


def model_config(config: dict, traffic: dict):
    """The program's configuration of a file's sizes."""
    import dataclasses
    from kungfu_tpu.models.gpt import GPTConfig
    lacks = {"d_head", "window", "n_experts"} - {
        f.name for f in dataclasses.fields(GPTConfig)}
    if lacks:       # a program from before the family: say so and leave
        raise SystemExit(f"models/gpt.py's GPTConfig has no "
                         f"{sorted(lacks)}: this program cannot run the "
                         f"smallthinker family")
    s = ref.sizes(config)
    if not s["norm_topk"]:
        raise ValueError("parallel/moe.py normalises the k weights a token; "
                         "this configuration states norm_topk_prob false")
    return GPTConfig(
        vocab_size=s["V"], d_model=s["D"], n_heads=s["H"], d_head=s["Dh"],
        n_kv_heads=s["Hkv"], n_layers=s["L"], d_ff=0,
        max_seq=traffic["seq_len"], dtype=jnp.bfloat16,
        rope=tuple(bool(r) for r in s["rope"]),
        window=tuple(s["window"] if w else None for w in s["windowed"]),
        mlp="reglu", norm_eps=s["eps"], rope_theta=float(s["theta"]),
        n_experts=s["E"], experts_per_token=s["k"], d_expert=s["F"],
        experts_held=(s["first"], s["G"]))


def held_rows_probe(config: dict, traffic: dict):
    """`probe(params, tokens) -> [layers]`: the program's counter of the
    assignments to held experts (`gpt.held_rows`), jitted; `params` are a
    job's lane-stacked weights, `tokens` [batch, seq_len]."""
    from kungfu_tpu.models.gpt import held_rows
    cfg = model_config(config, traffic)
    return jax.jit(lambda params, tokens: held_rows(
        jax.tree_util.tree_map(lambda t: t[0], params), tokens, cfg,
        attn="flash"))


def build(config: dict, traffic: dict, mesh) -> program.Job:
    from kungfu_tpu.models.gpt import forward_features
    from kungfu_tpu.ops.chunked_ce import chunked_cross_entropy
    from kungfu_tpu.training import build_train_step, init_opt_state

    cfg = model_config(config, traffic)
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
