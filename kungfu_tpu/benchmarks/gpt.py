"""GPT causal-LM training-throughput benchmark (tokens/sec/chip).

The LLM-era companion to the allreduce microbench: measures end-to-end
training step time of the GPT family (models/gpt.py) through the same
distributed train-step path users run — synchronous-SGD wrapper over a
mesh, flash attention on TPU — and reports tokens/sec plus model FLOPs
utilisation (6*N*T FLOPs/token approximation).

The reference has no LLM benchmark (its fixtures stop at BERT gradient
*sizes*, srcs/python/kungfu/tensorflow/v1/benchmarks/model_sizes.py); this
extends the harness to the model family the TPU framework treats as its
flagship.

Usage:
    python -m kungfu_tpu.benchmarks.gpt                    # gpt-small-ish
    python -m kungfu_tpu.benchmarks.gpt --d-model 1024 --n-layers 24 \
        --seq 2048 --batch 8 --rope --swiglu
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


# one-flag reproductions of the README's headline rows; every field can
# still be overridden by an explicit flag AFTER --preset
PRESETS = {
    "164m": ["--seq", "2048", "--batch", "64", "--n-kv-heads", "4",
             "--rope", "--swiglu", "--accum", "16",
             "--chunked-ce", "16384"],
    "470m": ["--d-model", "1024", "--n-layers", "24", "--n-heads", "16",
             "--n-kv-heads", "4", "--d-ff", "4096", "--seq", "2048",
             "--batch", "64", "--rope", "--swiglu", "--accum", "32",
             "--chunked-ce", "16384"],
    "164m-long": ["--seq", "8192", "--batch", "16", "--n-kv-heads", "4",
                  "--rope", "--swiglu", "--accum", "16",
                  "--chunked-ce", "8192"],
    # -hd128 variants: same d_model/d_ff/params but head_dim 128 —
    # 128-wide heads fill the MXU contraction (ROOFLINE.json: flash fwd
    # 56.1 vs 29.5 TFLOP/s at hd64), the high-MFU configurations.  KV
    # width is unchanged (2x128 = 4x64 bytes), so cache size and param
    # count match the hd64 presets exactly.  Measured (v5e): 164m 51%
    # -> 70% MFU, 164m-long 38% -> 62%, 470m 52% -> 68%
    "164m-hd128": ["--seq", "2048", "--batch", "64", "--n-heads", "6",
                   "--n-kv-heads", "2", "--rope", "--swiglu",
                   "--accum", "16", "--chunked-ce", "16384"],
    "164m-long-hd128": ["--seq", "8192", "--batch", "16",
                        "--n-heads", "6", "--n-kv-heads", "2",
                        "--rope", "--swiglu", "--accum", "16",
                        "--chunked-ce", "8192"],
    "470m-hd128": ["--d-model", "1024", "--n-layers", "24",
                   "--n-heads", "8", "--n-kv-heads", "2",
                   "--d-ff", "4096", "--seq", "2048", "--batch", "64",
                   "--rope", "--swiglu", "--accum", "32",
                   "--chunked-ce", "16384"],
}


def parse_args(argv=None):
    if argv is None:
        import sys as _sys
        argv = _sys.argv[1:]
    # pre-parse --preset (handles both "--preset X" and "--preset=X")
    # and splice its flags FIRST so explicit flags win
    pre = argparse.ArgumentParser(add_help=False)
    pre.add_argument("--preset", choices=list(PRESETS))
    known, rest = pre.parse_known_args(list(argv))
    argv = (PRESETS[known.preset] + rest) if known.preset else rest
    p = argparse.ArgumentParser(description="GPT training throughput")
    p.add_argument("--preset", choices=list(PRESETS), default=None,
                   help="flag bundle reproducing a README benchmark row "
                        "(applied before other flags, which override it)")
    # the pre-parser consumed --preset from argv; carry the value through
    # so args.preset records which README row actually ran
    p.set_defaults(preset=known.preset)
    p.add_argument("--vocab", type=int, default=32768)
    p.add_argument("--d-model", type=int, default=768)
    p.add_argument("--n-layers", type=int, default=12)
    p.add_argument("--n-heads", type=int, default=12)
    p.add_argument("--n-kv-heads", type=int, default=0,
                   help="GQA KV heads (0 = MHA)")
    p.add_argument("--d-ff", type=int, default=3072)
    p.add_argument("--seq", type=int, default=1024)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--warmup-steps", type=int, default=3)
    p.add_argument("--rope", action="store_true")
    p.add_argument("--swiglu", action="store_true")
    p.add_argument("--remat", nargs="?", const="full", default="",
                   choices=["", "none", "full", "attn", "ffn"],
                   help="per-layer rematerialization: 'full' saves each "
                        "block's input, the flash kernel's output and lse "
                        "(the backward never re-runs the kernel) and, "
                        "under output norms, the FFN's output projection; "
                        "'attn' saves all of the attention's residuals "
                        "(q, k, v too) and recomputes the projections, "
                        "norms and FFN; 'ffn' recomputes only the "
                        "norm+FFN sub-block")
    p.add_argument("--attn", default="auto",
                   help="auto | flash | dense")
    p.add_argument("--f32", action="store_true",
                   help="float32 instead of bfloat16")
    p.add_argument("--decode", action="store_true",
                   help="measure KV-cache autoregressive generation "
                        "instead of training")
    p.add_argument("--chunked-ce", type=int, default=0, metavar="CHUNK",
                   help="compute the loss with chunked-vocab cross-entropy "
                        "(no [B,T,V] logits tensor); value = vocab chunk")
    p.add_argument("--accum", type=int, default=1,
                   help="gradient-accumulation microbatches per step")
    p.add_argument("--prompt-len", type=int, default=128,
                   help="decode mode: prompt length to prefill")
    return p.parse_args(argv)


def param_count(params) -> int:
    import jax
    return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params))


def main(argv=None) -> int:
    args = parse_args(argv)

    import jax
    import jax.numpy as jnp
    import optax

    import kungfu_tpu.optimizers as kfopt
    from kungfu_tpu.comm.mesh import flat_mesh
    from kungfu_tpu.models.gpt import GPTConfig, forward_local, init_params
    from kungfu_tpu.training import (build_train_step, init_opt_state,
                                     replicate)
    from kungfu_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = GPTConfig(vocab_size=args.vocab, d_model=args.d_model,
                    n_heads=args.n_heads, n_layers=args.n_layers,
                    d_ff=args.d_ff, max_seq=args.seq,
                    dtype=jnp.float32 if args.f32 else jnp.bfloat16,
                    n_kv_heads=args.n_kv_heads or None,
                    rope=args.rope,
                    mlp="swiglu" if args.swiglu else "gelu")

    if args.accum < 1 or (not args.decode and args.batch % args.accum):
        raise SystemExit(f"--accum {args.accum} must be >= 1 and divide "
                         f"--batch {args.batch}")

    params = init_params(jax.random.PRNGKey(0), cfg)
    n_params = param_count(params)

    if args.decode:
        if (args.attn != "auto" or args.remat not in ("", "none")
                or args.chunked_ce or args.accum != 1):
            raise SystemExit("--attn/--remat/--chunked-ce/--accum apply to "
                             "training "
                             "only; the decode loop always runs dense "
                             "per-token attention over the KV cache")
        return _decode_bench(args, cfg, params, n_params)

    mesh = flat_mesh(n=1)
    rng = np.random.RandomState(0)
    toks = jnp.asarray(
        rng.randint(0, cfg.vocab_size, (args.batch, args.seq)), jnp.int32)
    tgts = jnp.roll(toks, -1, axis=1)

    if args.chunked_ce:
        from kungfu_tpu.models.gpt import forward_features
        from kungfu_tpu.ops.chunked_ce import chunked_cross_entropy

        def loss_fn(p, batch):
            bt, by = batch
            feats = forward_features(p, bt, cfg, attn=args.attn,
                                     remat=args.remat)
            # head in the model dtype: bf16 x bf16 chunk matmuls hit the
            # fast MXU path (f32 accumulation via preferred_element_type
            # inside the op); the f32 master weight stays in params
            head = p["lm_head"].astype(cfg.dtype)
            return chunked_cross_entropy(feats, head, by,
                                         args.chunked_ce).mean()
    else:
        def loss_fn(p, batch):
            bt, by = batch
            logits = forward_local(p, bt, cfg, attn=args.attn,
                                   remat=args.remat)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, by).mean()

    opt = kfopt.synchronous_sgd(optax.adamw(3e-4))
    sp = replicate(params, mesh)
    st = init_opt_state(opt, sp, mesh)
    step = build_train_step(loss_fn, opt, mesh, donate=True,
                            accum_steps=args.accum,
                            compute_dtype=None if args.f32 else cfg.dtype)

    for _ in range(args.warmup_steps):
        sp, st, loss = step(sp, st, (toks, tgts))
    if args.warmup_steps:
        float(np.asarray(loss)[0])  # host fetch waits for the device

    t0 = time.perf_counter()
    for _ in range(args.steps):
        sp, st, loss = step(sp, st, (toks, tgts))
    final_loss = float(np.asarray(loss)[0])
    dt = time.perf_counter() - t0

    tokens = args.batch * args.seq * args.steps
    tok_per_sec = tokens / dt
    # 6ND fwd+bwd FLOPs/token + attention term 12*L*D*T (causal halved)
    flops_per_tok = 6 * n_params + 6 * cfg.n_layers * cfg.d_model * args.seq
    tflops = tok_per_sec * flops_per_tok / 1e12
    out = {
        "metric": "gpt_tokens_per_sec_per_chip",
        "value": round(tok_per_sec, 1),
        "unit": "tokens/sec/chip",
        "params": n_params,
        "model_tflops_per_sec": round(tflops, 2),
        "loss": round(final_loss, 4),
        "backend": jax.default_backend(),
    }
    print(json.dumps(out))
    return 0


def _decode_bench(args, cfg, params, n_params) -> int:
    """KV-cache autoregressive generation throughput: prefill a prompt,
    then greedy-decode ``--seq - --prompt-len`` new tokens."""
    import jax
    import jax.numpy as jnp

    from kungfu_tpu.models.gpt import generate

    if args.prompt_len <= 0:
        raise SystemExit("--prompt-len must be positive in decode mode")
    n_new = args.seq - args.prompt_len
    if n_new <= 0:
        raise SystemExit("--seq must exceed --prompt-len in decode mode")
    rng = np.random.RandomState(0)
    prompt = jnp.asarray(
        rng.randint(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)

    # NOTE: serving bf16-cast params measured ~30% SLOWER than the f32
    # masters here (11.9k -> 8.4k tok/s at batch 16) — XLA already hoists
    # the per-use bf16 casts out of the decode scan, and the pre-cast
    # form loses the fusion.  Don't "optimize" this without re-measuring.
    gen = jax.jit(lambda p, t: generate(p, cfg, t, n_new,
                                        max_len=args.seq))
    out = np.asarray(gen(params, prompt))  # compile + warm

    t0 = time.perf_counter()
    for _ in range(args.steps):
        out = gen(params, prompt)
    np.asarray(out)
    dt = time.perf_counter() - t0

    tok_per_sec = args.batch * n_new * args.steps / dt
    print(json.dumps({
        "metric": "gpt_decode_tokens_per_sec_per_chip",
        "value": round(tok_per_sec, 1),
        "unit": "tokens/sec/chip",
        "params": n_params,
        "prompt_len": args.prompt_len,
        "new_tokens": n_new,
        "batch": args.batch,
        "reps": args.steps,
        "backend": jax.default_backend(),
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
