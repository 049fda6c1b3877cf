"""On-chip prefix-cache benchmark: shared-prefix serving, cache on/off.

The workload the cache exists for: N requests sharing one long prompt
prefix (system prompt / few-shot template) with short unique suffixes.
Cache off, every admission pays the full-prompt prefill; cache on, the
prefix's dense compute runs once and later admissions prefill only
their suffix (prefill_group=1 so admissions are sequential — batched
co-admissions cannot share, see DecodeEngine docstring).

Token exactness is MEASURED, in two arms, with a quantified tie-margin
analysis (round-4 verdict #3):

- **trained** (the headline): the model is first trained on-chip to
  memorize a deterministic token-chain (bigram) task, giving it the
  confident, large-margin logits of a real trained model; prompts are
  chains from the same distribution.  Expectation: cached and uncached
  paths emit identical tokens, because the bf16 ulp differences between
  the dense full-prompt attend and the gathered suffix attend are
  orders of magnitude below the argmax margin.
- **random_init control**: seed-initialized weights produce
  near-uniform logits whose top-1/top-2 margins sit at the bf16 noise
  floor, so a fraction of tokens flip — the situation any
  paged-vs-contiguous attention stack shares.

For every emitted token the analysis teacher-forces the prompt+output
through an f32 forward and records the top1-top2 logit margin, so the
artifact shows divergences happen only at near-ties (margin comparable
to bf16 resolution) and vanish at trained-model margins.

    python tools/bench_prefix_cache.py          # writes PREFIX_BENCH.json
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

# deterministic affine token chain over a small alphabet: next(x) is a
# fixed permutation-ish map, so a model that has learned it predicts
# every non-restart token with near-certainty (the margin regime of a
# trained LM on its own domain)
_P = 509  # prime alphabet size; token ids 1.._P


def _chain_next(x):
    return 1 + ((5 * (x - 1) + 7) % _P)


def _chain(start, n):
    out = [start]
    for _ in range(n - 1):
        out.append(_chain_next(out[-1]))
    return out


def _train_chain_model(params, cfg, steps=200, batch=8, seq=512,
                       lr=3e-4, seed=7):
    """Train the model on-chip to memorize the chain task (restarts
    every ~64 tokens teach it to recover after a jump).  Trains f32
    master weights (bf16 adam state would stall at this task's tail
    loss), returns params in their ORIGINAL dtypes.  loss ~=
    (1/64)*ln(509) ~= 0.1 when learned."""
    import dataclasses

    import optax

    from kungfu_tpu.models import gpt as G

    orig_dtypes = jax.tree_util.tree_map(lambda t: t.dtype, params)
    params = jax.tree_util.tree_map(
        lambda t: t.astype(jnp.float32) if t.dtype == jnp.bfloat16 else t,
        params)
    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32)
    opt = optax.adam(lr)
    state = jax.jit(opt.init)(params)

    def loss_fn(p, toks):
        logits = G.forward_local(p, toks[:, :-1], cfg32)
        return G.parallel_cross_entropy(logits, toks[:, 1:]).mean()

    @jax.jit
    def step(p, s, toks):
        loss, g = jax.value_and_grad(loss_fn)(p, toks)
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s, loss

    rng = np.random.RandomState(seed)

    def make_batch():
        out = np.empty((batch, seq + 1), np.int32)
        for b in range(batch):
            row = []
            while len(row) < seq + 1:
                row += _chain(int(rng.randint(1, _P + 1)),
                              int(rng.randint(32, 96)))
            out[b] = row[:seq + 1]
        return jnp.asarray(out)

    loss = None
    for i in range(steps):
        params, state, loss = step(params, state, make_batch())
    final = float(np.asarray(loss))
    del state
    params = jax.tree_util.tree_map(
        lambda t, d: t.astype(d), params, orig_dtypes)
    return params, final


def _margins_f32(params, cfg, prompts, outputs):
    """Teacher-forced f32 top1-top2 logit margins at every emission
    position: {uid: [margin per emitted token]}.  One batched forward
    (every workload row has the same prompt+output length)."""
    import dataclasses

    from kungfu_tpu.models import gpt as G
    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32)
    p32 = jax.tree_util.tree_map(
        lambda t: t.astype(jnp.float32) if t.dtype == jnp.bfloat16 else t,
        params)

    @jax.jit
    def top2(p, toks):
        # reduce to [rows, T, 2] on device: the full f32 logits tensor
        # would be ~6 GB at this workload
        logits = G.forward_local(p, toks, cfg32)
        vals, _ = jax.lax.top_k(logits, 2)
        return vals

    uids = sorted(prompts)
    batch = np.asarray([prompts[u] + outputs[u] for u in uids], np.int32)
    t2 = np.asarray(top2(p32, jnp.asarray(batch)))
    out = {}
    for r, uid in enumerate(uids):
        plen = len(prompts[uid])
        out[uid] = [float(t2[r, plen - 1 + i, 0] - t2[r, plen - 1 + i, 1])
                    for i in range(len(outputs[uid]))]
    return out


def _arm(params, cfg, prompts, n_requests, max_new, measure_margins=True,
         buckets=(64, 4096)):
    """Serve the workload cache-off and cache-on; return the metrics
    dict (perf + agreement + margin analysis)."""
    from kungfu_tpu.serving import DecodeEngine, Request

    def reqs(uid0=0):
        return [Request(uid=uid0 + i, prompt=prompts[i], max_new=max_new)
                for i in range(n_requests)]

    def make(prefix_cache: bool):
        eng = DecodeEngine(params, cfg, num_slots=4, block_size=64,
                           num_blocks=320, prompt_buckets=buckets,
                           decode_chunk=8, prefill_group=1,
                           prefix_cache=prefix_cache)
        # warm pass: compiles every steady-state program (fresh-prefill
        # bucket, cached-prefill at the suffix AND partial-hit buckets)
        # and populates the cache; the timed passes are steady-state
        eng.run(reqs(uid0=100_000))
        eng.stats.reset()
        return eng

    def timed(eng):
        eng.stats.reset()
        t0 = time.perf_counter()
        out = eng.run(reqs())
        dt = time.perf_counter() - t0
        toks = sum(len(v) for v in out.values())
        return dt, {"tokens_out": toks,
                    "prefills": eng.stats.prefills,
                    "prefix_hits": eng.stats.prefix_hits,
                    "prefix_tokens_reused":
                        eng.stats.prefix_tokens_reused}, out

    # ALTERNATE the arms, best-of-3 (the repo's drift rule — chip
    # throughput swings tens of percent across minutes, so sequential
    # off-then-on would measure the drift window, not the cache)
    eng_off, eng_on = make(False), make(True)
    walls_off, walls_on = [], []
    out_off = out_on = None
    off = on = None
    for _ in range(3):
        dt, off, out_off = timed(eng_off)
        walls_off.append(dt)
        dt, on, out_on = timed(eng_on)
        walls_on.append(dt)
    for d, walls in ((off, walls_off), (on, walls_on)):
        d["wall_s"] = round(min(walls), 3)
        d["wall_s_all"] = [round(w, 3) for w in walls]
        d["tok_per_s"] = round(d["tokens_out"] / min(walls), 1)
    del eng_off, eng_on
    agree = sum(out_off[u] == out_on[u] for u in out_off)
    first_div = {}
    for u in out_off:
        if out_off[u] != out_on[u]:
            i = next(i for i, (a, b) in enumerate(
                zip(out_off[u], out_on[u])) if a != b)
            first_div[str(u)] = i
    doc = {"cache_off": off, "cache_on": on,
           "speedup": round(off["wall_s"] / on["wall_s"], 2),
           "requests_token_identical": f"{agree}/{len(out_off)}",
           "first_divergence_index": first_div or None}
    if measure_margins:
        margins = _margins_f32(params, cfg, prompts, out_off)
        agree_ms, div_ms = [], []
        for u in out_off:
            div_at = (first_div.get(str(u)))
            for i, m in enumerate(margins[u]):
                # positions past the first divergence compare different
                # contexts and say nothing about ties; drop them
                if div_at is not None and i > div_at:
                    break
                (div_ms if i == div_at else agree_ms).append(m)
        doc["margin_f32"] = {
            "agree_min": round(min(agree_ms), 4) if agree_ms else None,
            "agree_median": round(float(np.median(agree_ms)), 4)
            if agree_ms else None,
            "at_divergence": [round(m, 4) for m in sorted(div_ms)] or None,
        }
    return doc


def run(n_requests=12, prefix_len=3968, suffix_len=32, max_new=8,
        train_steps=200, out_path="PREFIX_BENCH.json"):
    from kungfu_tpu.models import gpt as G

    plat = jax.devices()[0].platform
    dtype = jnp.bfloat16 if plat == "tpu" else jnp.float32
    # compute-bound prefill shapes: a short prefill of a small model is
    # so little device time that the fixed cost of a dispatch hides the
    # saved prefix FLOPs.  At ~4k prefix tokens x 200M params the full
    # prefill is real compute per admission.
    cfg = G.GPTConfig(vocab_size=32768, d_model=1024, n_heads=8,
                      n_kv_heads=4, n_layers=12, d_ff=4096, max_seq=4096,
                      rope=True, mlp="swiglu", dtype=dtype)
    params0 = G.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)

    # one shared prefix chain; unique suffixes = chain restarts (the
    # restart token itself is inside the PROMPT, so every EMITTED token
    # is chain-predictable for a model that learned the map)
    prefix = _chain(int(rng.randint(1, _P + 1)), prefix_len)
    prompts = {i: prefix + _chain(int(rng.randint(1, _P + 1)), suffix_len)
               for i in range(n_requests)}

    doc = {"platform": plat, "device": str(jax.devices()[0]),
           "workload": {"n_requests": n_requests, "prefix_len": prefix_len,
                        "suffix_len": suffix_len, "max_new": max_new,
                        "params_m": 200,
                        "task": f"affine token chain mod {_P}"}}

    # --- headline arm: TRAINED weights --------------------------------
    t0 = time.perf_counter()
    params, final_loss = _train_chain_model(params0, cfg,
                                            steps=train_steps)
    doc["trained"] = {"train_steps": train_steps,
                      "train_wall_s": round(time.perf_counter() - t0, 1),
                      "final_loss": round(final_loss, 4)}
    doc["trained"].update(_arm(params, cfg, prompts, n_requests, max_new))
    del params

    # --- control arm: random init (degenerate near-uniform logits) ----
    doc["random_init_control"] = _arm(params0, cfg, prompts, n_requests,
                                      max_new)

    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(json.dumps(doc, indent=2))
    return doc


if __name__ == "__main__":
    run(out_path=sys.argv[1] if len(sys.argv) > 1 else "PREFIX_BENCH.json")
