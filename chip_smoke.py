"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one process, every local chip

Drives the three paths the benchmark will make cells of — ResNet-50
training, GPT training with the Pallas flash kernel, serving through
``DecodeEngine`` with the Pallas paged kernel — plus the kernels alone
against their references and, on two or more chips, an elastic
shrink/grow, each through the entry points a user calls and at the full
width of a model the repo supports (the ``470m`` preset of
benchmarks/gpt.py; ResNet-50).  Batch, steps and serving depth are cut
for time; widths are not.  Weights are random, from a seed.

It requires ``jax.default_backend() == "tpu"`` and a device kind it
knows; anything else exits non-zero before any phase and prints no
result.  ``--tiny`` runs the same phases at toy widths on whatever
platform jax finds and labels its output so; it exists for the tests
and for rehearsing the command where there is no chip.

Output: platform / device kind / count, one line per phase, the JSON
summary (every phase's facts; ends with ``"claim": null``) and, as the
last line of stdout, the result the driver reads and nothing more:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Exit 0 only if every phase passed.  Seconds printed here are
information, never pass conditions and never a claim.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import gc
import json
import math
import sys
import time
import traceback
import urllib.error
import urllib.request
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import kungfu_tpu.optimizers as kfopt
from kungfu_tpu.comm.mesh import flat_mesh
from kungfu_tpu.elastic import ElasticTrainer
from kungfu_tpu.models import ResNet, ResNet50
from kungfu_tpu.models.gpt import (GPTConfig, forward_features, generate,
                                   init_params)
from kungfu_tpu.ops.chunked_ce import chunked_cross_entropy
from kungfu_tpu.ops.flash_attention import _expand_kv_heads, flash_attention
from kungfu_tpu.ops.paged_attention import (paged_attention,
                                            paged_attention_queries)
from kungfu_tpu.parallel import reference_attention
from kungfu_tpu.serving import DecodeEngine, Request, ServingServer
from kungfu_tpu.serving.cache import (pool_attend, pool_attend_queries,
                                      quantize_kv)
from kungfu_tpu.training import (build_train_step,
                                 build_train_step_with_state, init_opt_state,
                                 replicate)
from kungfu_tpu.utils.compile_cache import (CompileCounter,
                                            enable_compile_cache)

KNOWN_DEVICE_KINDS = ("TPU v5 lite",)
MOSAIC_CALL = "tpu_custom_call"   # what a lowered Pallas TPU kernel is

# tolerances are the repo's own: tests/test_flash_attention.py
# (test_flash_bf16) and tests/test_paged_attention.py
# (test_kernel_bf16_runs) for bf16 kernels against the f32 reference,
# tests/test_kv_int8.py (test_int8_engine_tokens_track_fp_engine) for
# greedy tokens of two engines that may flip near-tie argmaxes
BF16_TOL = 5e-2
TOKEN_AGREEMENT = 0.75
# bf16 gradients have no elementwise tolerance in the tests (they check
# f32); relative Frobenius error against the f32 reference instead
GRAD_REL_ERR = 2e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    tiny: bool
    # train-resnet / elastic: None = ResNet-50, else toy stage sizes
    resnet_stages: Optional[Tuple[int, ...]]
    image: int
    resnet_batch: int          # images per chip (and per elastic lane)
    resnet_steps: int
    # the GPT model of train-gpt and serve (GPTConfig fields)
    gpt: Dict[str, object]
    gpt_seqs: int              # sequences per chip per step
    gpt_micro: int             # sequences per microbatch
    gpt_steps: int
    ce_chunk: int
    # kernels: (B, T, H, KVH, D) and (S, H, KVH, Dh, block, max_blocks)
    flash_cases: Tuple[Tuple[int, ...], ...]
    paged_cases: Tuple[Tuple[int, ...], ...]
    # serve
    serve_layers: int
    slots: int
    block: int
    chunk: int
    max_len: int
    buckets: Tuple[int, ...]
    exact_requests: Tuple[Tuple[int, int], ...]   # (prompt_len, max_new)
    mixed_requests: Tuple[Tuple[int, int], ...]


# the 470m preset (kungfu_tpu/benchmarks/gpt.py PRESETS["470m"])
_GPT_470M = dict(vocab_size=32768, d_model=1024, n_heads=16, n_kv_heads=4,
                 n_layers=24, d_ff=4096, max_seq=2048, rope=True,
                 mlp="swiglu")

FULL = Sizes(
    tiny=False,
    resnet_stages=None, image=224, resnet_batch=256, resnet_steps=5,
    gpt=_GPT_470M, gpt_seqs=8, gpt_micro=2, gpt_steps=3, ce_chunk=16384,
    # head widths and lengths of the presets: 470m (16/4 x 64 @ 2048),
    # 470m-hd128 (8/2 x 128 @ 2048), 164m-long and 164m-long-hd128
    # (x 64 and x 128 @ 8192; fewer heads, so the dense f32 reference
    # fits beside the kernel — heads are a parallel grid axis)
    flash_cases=((2, 2048, 16, 4, 64), (2, 2048, 8, 2, 128),
                 (1, 8192, 4, 2, 64), (1, 8192, 2, 1, 128)),
    # the 470m and 470m-hd128 KV shapes under the serve phase's layout
    paged_cases=((24, 16, 4, 64, 32, 32), (24, 8, 2, 128, 32, 32)),
    serve_layers=6, slots=24, block=32, chunk=16, max_len=1024,
    buckets=(64, 512),
    exact_requests=((9, 24), (60, 33), (60, 17), (200, 20)),
    mixed_requests=((5, 6), (17, 8), (40, 8), (100, 6), (130, 8),
                    (300, 6), (64, 8), (23, 4)),
)

TINY = Sizes(
    tiny=True,
    resnet_stages=(1, 1), image=32, resnet_batch=4, resnet_steps=2,
    gpt=dict(vocab_size=256, d_model=32, n_heads=4, n_kv_heads=2,
             n_layers=2, d_ff=64, max_seq=64, rope=True, mlp="swiglu"),
    gpt_seqs=4, gpt_micro=2, gpt_steps=3, ce_chunk=128,
    flash_cases=((1, 64, 4, 2, 16),),
    paged_cases=((3, 4, 2, 16, 8, 4),),
    serve_layers=2, slots=3, block=8, chunk=4, max_len=64,
    buckets=(8, 32),
    exact_requests=((3, 6), (11, 5), (11, 4)),
    mixed_requests=((2, 4), (7, 4), (12, 4), (20, 4)),
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _require_mosaic(lowered_text: str, what: str, at_least: int = 1) -> int:
    """Count the Mosaic calls in a lowered module; on a TPU fewer than
    ``at_least`` is a failure (interpret mode or a jnp twin must not
    pass for the kernel).  Off the TPU (``--tiny``) kernels run
    interpreted and there is nothing to count."""
    n = lowered_text.count(MOSAIC_CALL)
    if _on_tpu() and n < at_least:
        raise AssertionError(
            f"{what}: {n} Mosaic call(s) in the lowered module, "
            f"expected >= {at_least}")
    return n


def _finite(values: Sequence[float], what: str) -> None:
    if not all(math.isfinite(v) for v in values):
        raise AssertionError(f"{what}: non-finite loss in {values}")


def _batch_sharding(mesh):
    return NamedSharding(mesh, P(mesh.axis_names))


# ---------------------------------------------------------------- resnet
def _resnet_sgd():
    return optax.sgd(0.1, momentum=0.9, nesterov=True)


def _resnet(sz: Sizes):
    """(loss_fn, params, batch_stats, make_batch)."""
    if sz.resnet_stages is None:
        model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    else:
        model = ResNet(stage_sizes=list(sz.resnet_stages), num_classes=10,
                       num_filters=8, dtype=jnp.float32, small_inputs=True)

    def make_batch(n_images: int, mesh):
        rng = np.random.RandomState(0)
        x = rng.rand(n_images, sz.image, sz.image, 3).astype(np.float32)
        y = rng.randint(0, 10, size=n_images).astype(np.int32)
        return jax.device_put((x, y), _batch_sharding(mesh))

    variables = jax.jit(model.init)(
        jax.random.PRNGKey(0),
        jnp.zeros((8, sz.image, sz.image, 3), jnp.float32))

    def loss_fn(p, mstate, b):
        bx, by = b
        logits, updated = model.apply({"params": p, "batch_stats": mstate},
                                      bx, train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, by).mean()
        return loss, updated["batch_stats"]

    return loss_fn, variables["params"], variables["batch_stats"], make_batch


def phase_train_resnet(sz: Sizes) -> dict:
    """bench.py's configuration over every local chip."""
    mesh = flat_mesh()
    n = mesh.devices.size
    loss_fn, params, bstats, make_batch = _resnet(sz)
    batch = make_batch(sz.resnet_batch * n, mesh)
    opt = kfopt.synchronous_sgd(_resnet_sgd())
    sp = replicate(params, mesh)
    sms = replicate(bstats, mesh)
    st = init_opt_state(opt, sp, mesh)
    step = build_train_step_with_state(loss_fn, opt, mesh)
    losses = []
    for _ in range(sz.resnet_steps):
        sp, st, sms, loss = step(sp, st, sms, batch)
        losses.append(loss)
    jax.block_until_ready(sp)
    losses = [float(np.asarray(l)[0]) for l in losses]
    _finite(losses, "train-resnet")
    return {"lanes": n, "images_per_step": sz.resnet_batch * n,
            "losses": [round(l, 4) for l in losses]}


# ------------------------------------------------------------------- gpt
def _gpt_cfg(sz: Sizes, dtype, n_layers: Optional[int] = None):
    fields = dict(sz.gpt, dtype=dtype)
    if n_layers is not None:
        fields["n_layers"] = n_layers
    return GPTConfig(**fields)


def _gpt_params(cfg):
    """f32 master weights from seed 0, as ONE compiled program."""
    return jax.jit(lambda k: init_params(k, cfg))(jax.random.PRNGKey(0))


def phase_train_gpt(sz: Sizes) -> dict:
    """benchmarks/gpt.py's training path with the flash kernel asked for
    by name: bf16 compute over f32 masters, chunked CE, adamw, gradient
    accumulation, donated state."""
    cfg = _gpt_cfg(sz, jnp.bfloat16)
    mesh = flat_mesh()
    n = mesh.devices.size
    rng = np.random.RandomState(0)
    toks = rng.randint(0, cfg.vocab_size,
                       (sz.gpt_seqs * n, cfg.max_seq)).astype(np.int32)
    batch = jax.device_put((toks, np.roll(toks, -1, axis=1)),
                           _batch_sharding(mesh))

    def loss_fn(p, b):
        bt, by = b
        feats = forward_features(p, bt, cfg, attn="flash")
        head = p["lm_head"].astype(cfg.dtype)
        return chunked_cross_entropy(feats, head, by, sz.ce_chunk).mean()

    opt = kfopt.synchronous_sgd(optax.adamw(3e-4))
    sp = replicate(_gpt_params(cfg), mesh)
    n_params = sum(int(np.prod(t.shape[1:]))
                   for t in jax.tree_util.tree_leaves(sp))
    st = init_opt_state(opt, sp, mesh)
    step = build_train_step(loss_fn, opt, mesh, donate=True,
                            accum_steps=sz.gpt_seqs // sz.gpt_micro,
                            compute_dtype=cfg.dtype)
    lowered = step.lower(sp, st, batch)
    mosaic = _require_mosaic(lowered.as_text(), "train-gpt step",
                             at_least=2)          # forward and backward
    run = lowered.compile()
    del lowered
    losses = []
    for _ in range(sz.gpt_steps):
        sp, st, loss = run(sp, st, batch)
        losses.append(loss)
    jax.block_until_ready(sp)
    losses = [float(np.asarray(l)[0]) for l in losses]
    _finite(losses, "train-gpt")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"train-gpt: loss did not fall on a fixed "
                             f"batch: {losses}")
    out = {"lanes": n, "params": n_params, "attn": "flash",
           "mosaic_calls": mosaic,
           "losses": [round(l, 4) for l in losses]}
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    if all(p is not None for p in peaks):
        out["peak_bytes_in_use"] = peaks
        if n > 1 and max(peaks) > 1.25 * min(peaks):
            raise AssertionError(
                f"train-gpt: device peak memory uneven: {peaks}")
    else:
        out["peak_bytes_in_use"] = "not reported by this backend"
    return out


# --------------------------------------------------------------- kernels
def _randn(rng, shape, dtype):
    """Host-side normal draws already in ``dtype``: the upload compiles
    nothing (an on-device convert would be one more program)."""
    return jnp.asarray(rng.randn(*shape).astype(dtype))


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def _flash_case(case) -> List[dict]:
    B, T, H, KVH, D = case
    g = H // KVH
    rng = np.random.RandomState(D + T)
    q = _randn(rng, (B, T, H, D), jnp.bfloat16)
    k = _randn(rng, (B, T, KVH, D), jnp.bfloat16)
    v = _randn(rng, (B, T, KVH, D), jnp.bfloat16)
    w = _randn(rng, (B, T, H, D), jnp.float32)            # loss weights

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, kv_groups=g)

    def dense(q, k, v):
        # the repo's oracle, on f32 copies so every matmul is f32
        f = lambda t: t.astype(jnp.float32)
        return reference_attention(f(q), _expand_kv_heads(f(k), g),
                                   _expand_kv_heads(f(v), g), causal=True)

    def with_grads(attend):
        def loss(q, k, v, w):
            out = attend(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * w), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))

    tag = f"flash D={D} T={T}"
    fwd_calls = _require_mosaic(
        jax.jit(flash).lower(q, k, v).as_text(), tag + " forward")
    flash_vg = with_grads(flash)
    # forward-with-lse, delta, dq, dk/dv
    bwd_calls = _require_mosaic(flash_vg.lower(q, k, v, w).as_text(),
                                tag + " backward", at_least=4)
    (_, out), grads = flash_vg(q, k, v, w)
    with jax.default_matmul_precision("float32"):
        (_, ref), ref_grads = with_grads(dense)(q, k, v, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=BF16_TOL,
                               atol=BF16_TOL, err_msg=tag)
    errs = {"out": _rel_err(out, ref)}
    for name, a, b in zip(("dq", "dk", "dv"), grads, ref_grads):
        errs[name] = _rel_err(a, b)
    bad = {n: e for n, e in errs.items() if not e < GRAD_REL_ERR}
    if bad:
        raise AssertionError(f"{tag}: relative error {bad} above "
                             f"{GRAD_REL_ERR}")
    return [{"case": tag, "mosaic_fwd": fwd_calls, "mosaic_bwd": bwd_calls,
             "rel_err": {n: float(f"{e:.2e}") for n, e in errs.items()}}]


def _paged_cases(case) -> List[dict]:
    """Both paged kernels, bf16 pool and int8 pool, against the gather
    path the engine runs with attend="gather"."""
    S, H, KVH, Dh, bs, MB = case
    Q = 3                                   # speculative=2: token + 2 drafts
    N = S * MB + 1
    rng = np.random.RandomState(Dh)
    kp = _randn(rng, (N, bs, KVH, Dh), jnp.bfloat16)
    vp = _randn(rng, (N, bs, KVH, Dh), jnp.bfloat16)
    # the engine's invariant: distinct non-scratch blocks for each
    # slot's allocated prefix, block 0 (scratch) beyond it
    pos = np.minimum(rng.randint(0, MB * bs, S), MB * bs - Q)
    tables = np.zeros((S, MB), np.int32)
    free = list(range(1, N))
    rng.shuffle(free)
    for s in range(S):
        for b in range((pos[s] + Q - 1) // bs + 1):
            tables[s, b] = free.pop()
    qpos = jnp.asarray(pos[:, None] + np.arange(Q)[None, :], jnp.int32)
    tables, pos = jnp.asarray(tables), jnp.asarray(pos.astype(np.int32))
    (kq, ks), (vq, vs) = jax.jit(
        lambda k, v: (quantize_kv(k), quantize_kv(v)))(kp, vp)
    pools = {"bf16": {"k": kp, "v": vp},
             "int8": {"k": kq, "ks": ks, "v": vq, "vs": vs}}

    def fused_one(q, pool):
        return paged_attention(q, pool["k"], pool["v"], tables, pos,
                               k_scale=pool.get("ks"),
                               v_scale=pool.get("vs"))

    def fused_many(q, pool):
        return paged_attention_queries(q, pool["k"], pool["v"], tables,
                                       pos, k_scale=pool.get("ks"),
                                       v_scale=pool.get("vs"))

    def gather_one(q, pool):
        return pool_attend(q[:, None], pool, tables, pos,
                           mode="gather")[:, 0]

    def gather_many(q, pool):
        return pool_attend_queries(q, pool, tables, qpos, mode="gather")

    kernels = (
        ("paged_attention", fused_one, gather_one,
         _randn(rng, (S, H, Dh), jnp.bfloat16)),
        ("paged_attention_queries", fused_many, gather_many,
         _randn(rng, (S, Q, H, Dh), jnp.bfloat16)),
    )
    out = []
    for pool_name, pool in pools.items():
        for name, fused, gather, q in kernels:
            tag = f"{name} {pool_name} KV={KVH}x{Dh} block={bs}"
            fused = jax.jit(fused)
            calls = _require_mosaic(fused.lower(q, pool).as_text(), tag)
            got = np.asarray(fused(q, pool), np.float32)
            want = np.asarray(jax.jit(gather)(q, pool), np.float32)
            np.testing.assert_allclose(got, want, rtol=BF16_TOL,
                                       atol=BF16_TOL, err_msg=tag)
            out.append({"case": tag, "mosaic": calls,
                        "rel_err": float(f"{_rel_err(got, want):.2e}")})
    return out


def phase_kernels(sz: Sizes) -> dict:
    """Every case runs even after one fails, so one run shows which
    shapes compile and which do not."""
    results, failed = [], []
    for fn, cases in ((_flash_case, sz.flash_cases),
                      (_paged_cases, sz.paged_cases)):
        for case in cases:
            try:
                results.extend(fn(case))
            except Exception:  # noqa: BLE001 — reported, then re-raised below
                traceback.print_exc()
                err = traceback.format_exc().strip().splitlines()[-1]
                failed.append({"case": f"{fn.__name__}{case}",
                               "error": err[:400]})
    if failed:
        raise AssertionError(f"kernels: {len(failed)} case(s) failed: "
                             f"{json.dumps(failed)}")
    return {"cases": results}


# ----------------------------------------------------------------- serve
def _generate_over_http(url: str, prompts, max_news) -> Dict[int, List[int]]:
    """POST every request concurrently; read EVERY response.  The server
    turns an engine failure into 503 bodies (serving/server.py
    ``_fatal``), so any non-200 or error body fails the phase."""
    def post(i):
        body = json.dumps({"prompt": prompts[i],
                           "max_new": max_news[i]}).encode()
        req = urllib.request.Request(
            url + "/generate", data=body,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=900) as r:
                return i, r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return i, e.code, e.read().decode(errors="replace")

    with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
        replies = [f.result() for f in
                   [pool.submit(post, i) for i in range(len(prompts))]]
    bad = [r for r in replies if r[1] != 200 or "tokens" not in r[2]]
    if bad:
        raise AssertionError(f"serve: {len(bad)} request(s) failed: {bad}")
    got = {i: body["tokens"] for i, _, body in replies}
    short = {i: (len(t), max_news[i]) for i, t in got.items()
             if len(t) != max_news[i]}
    if short:
        raise AssertionError(f"serve: wrong token counts {short}")
    return got


def _engine(params, cfg, sz: Sizes, attend: str, **engine_kw):
    return DecodeEngine(params, cfg, attend=attend, num_slots=sz.slots,
                        block_size=sz.block, decode_chunk=sz.chunk,
                        num_blocks=sz.slots * (sz.max_len // sz.block) + 1,
                        max_len=sz.max_len, prompt_buckets=sz.buckets,
                        **engine_kw)


def _serve(params, cfg, sz: Sizes, prompts, max_news, **engine_kw):
    """One server over one fused-attend engine, in this process."""
    srv = ServingServer(_engine(params, cfg, sz, "fused", **engine_kw),
                        port=0).start()
    url = f"http://{srv.host}:{srv.port}"
    try:
        got = _generate_over_http(url, prompts, max_news)
        with urllib.request.urlopen(url + "/stats", timeout=60) as r:
            stats = json.loads(r.read())
    finally:
        srv.close()
    return got, stats


def _gather_oracle(params, cfg, sz: Sizes, prompts, max_news, **engine_kw):
    return _engine(params, cfg, sz, "gather", **engine_kw).run(
        [Request(uid=i, prompt=p, max_new=m)
         for i, (p, m) in enumerate(zip(prompts, max_news))])


def _agreement(a: Dict[int, List[int]], b: Dict[int, List[int]]) -> float:
    same = sum(x == y for u in a for x, y in zip(a[u], b[u]))
    return same / sum(len(v) for v in a.values())


def phase_serve(sz: Sizes) -> dict:
    rng = np.random.RandomState(1)
    vocab = sz.gpt["vocab_size"]

    def requests(spec):
        return ([rng.randint(0, vocab, n).tolist() for n, _ in spec],
                [m for _, m in spec])

    out = {"layers": sz.serve_layers, "attend": "fused"}
    params = _gpt_params(_gpt_cfg(sz, jnp.float32, sz.serve_layers))

    # f32, every matmul f32: tokens must EQUAL models.gpt.generate.  The
    # precision is process-wide config (not the context manager) because
    # the server's scheduler thread traces the engine's programs.
    cfg32 = _gpt_cfg(sz, jnp.float32, sz.serve_layers)
    prompts, max_news = requests(sz.exact_requests)
    jax.config.update("jax_default_matmul_precision", "float32")
    try:
        got, stats = _serve(params, cfg32, sz, prompts, max_news)
        # one compiled generate per prompt length, run to the longest
        # continuation asked of that length (greedy: a prefix of it is
        # the shorter request's answer)
        longest: Dict[int, int] = {}
        for p, m in zip(prompts, max_news):
            longest[len(p)] = max(m, longest.get(len(p), 0))
        # (weights are an ARGUMENT: closed over, they would be baked
        # into each program as constants — 0.6 GB apiece at this size)
        solo = {t: jax.jit(lambda w, pr, t=t: generate(
            w, cfg32, pr, longest[t], max_len=sz.max_len))
            for t in longest}
        for i, (p, m) in enumerate(zip(prompts, max_news)):
            want = np.asarray(solo[len(p)](
                params, jnp.asarray([p], jnp.int32)))[0, :m].tolist()
            if got[i] != want:
                raise AssertionError(
                    f"serve f32: request {i} (prompt {len(p)}, "
                    f"max_new {m}) differs from generate: "
                    f"{got[i]} != {want}")
    finally:
        jax.config.update("jax_default_matmul_precision", None)
    out["f32_equals_generate"] = len(prompts)
    out["f32_stats"] = {k: stats[k] for k in ("tokens_out", "prefills",
                                              "dispatches")}

    # bf16 and int8-KV: the same requests complete, and agree with the
    # gather path as far as two bf16 engines can (near-tie argmaxes
    # flip: docs/serving.md)
    cfg16 = _gpt_cfg(sz, jnp.bfloat16, sz.serve_layers)
    prompts, max_news = requests(sz.mixed_requests)
    for name, kw in (("bf16", {}), ("int8_kv", {"kv_dtype": jnp.int8})):
        got, _ = _serve(params, cfg16, sz, prompts, max_news, **kw)
        want = _gather_oracle(params, cfg16, sz, prompts, max_news, **kw)
        agree = _agreement(got, want)
        out[f"{name}_agreement_with_gather"] = round(agree, 3)
        if agree < TOKEN_AGREEMENT:
            raise AssertionError(
                f"serve {name}: fused and gather engines agree on "
                f"{agree:.2f} of tokens, below {TOKEN_AGREEMENT}: "
                f"{got} vs {want}")
    return out


# --------------------------------------------------------------- elastic
def _lanes_identical(tree, what: str) -> None:
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        if not all(np.array_equal(a[0], a[i]) for i in range(1, len(a))):
            raise AssertionError(
                f"elastic: {what} lanes differ at "
                f"{jax.tree_util.keystr(path)}")


def phase_elastic(sz: Sizes) -> dict:
    n = len(jax.devices())
    if n < 2:
        return {"not_run": f"device_count={n}"}
    loss_fn, params, bstats, make_batch = _resnet(sz)
    # train-resnet's optimizer and per-lane batch: the n-lane step is
    # the program that phase already compiled
    tr = ElasticTrainer(
        loss_fn,
        optimizer_factory=lambda _n: kfopt.synchronous_sgd(_resnet_sgd()),
        init_params=params, init_model_state=bstats, init_size=n)
    losses = []
    for size in (n, n // 2, n):
        tr.resize(size)
        losses.append(tr.step(make_batch(sz.resnet_batch * size, tr.mesh)))
    _finite(losses, "elastic")
    if tr.n != n or tr.version != 2:
        raise AssertionError(f"elastic: ended at size {tr.n}, "
                             f"version {tr.version}")
    _lanes_identical(tr.params, "params")
    _lanes_identical(tr.model_state, "batch-norm state")
    _lanes_identical(tr.opt_state, "optimizer state")
    out = {"lanes": n, "schedule": [n, n // 2, n],
           "losses": [round(l, 4) for l in losses],
           "lanes_bit_identical": True}

    # the paper's two other optimizers, one step each on the same mesh:
    # pair averaging is the only user of ppermute
    mesh = flat_mesh()
    batch = make_batch(sz.resnet_batch * n, mesh)
    for name, opt in (
            ("synchronous_averaging",
             kfopt.synchronous_averaging(_resnet_sgd())),
            ("pair_averaging", kfopt.pair_averaging(_resnet_sgd(), n=n))):
        sp = replicate(params, mesh)
        sms = replicate(bstats, mesh)
        st = init_opt_state(opt, sp, mesh)
        step = build_train_step_with_state(loss_fn, opt, mesh)
        sp, st, sms, loss = step(sp, st, sms, batch)
        loss = float(np.asarray(loss)[0])
        _finite([loss], name)
        out[name + "_loss"] = round(loss, 4)
    return out


PHASES: List[Tuple[str, Callable[[Sizes], dict]]] = [
    ("train-resnet", phase_train_resnet),
    ("train-gpt", phase_train_gpt),
    ("kernels", phase_kernels),
    ("serve", phase_serve),
    ("elastic", phase_elastic),
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="toy widths on whatever platform jax finds "
                         "(tests, rehearsal); never a chip result")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not args.tiny and (jax.default_backend() != "tpu"
                          or device["kind"] not in KNOWN_DEVICE_KINDS):
        print(f"chip_smoke: needs a TPU of a known kind "
              f"{KNOWN_DEVICE_KINDS}; jax found {device}. No phase ran.",
              file=sys.stderr)
        return 2
    sz = TINY if args.tiny else FULL
    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    print(f"chip_smoke: platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']} "
          f"tiny={sz.tiny} compile_cache={cache_dir}", flush=True)

    phases: Dict[str, dict] = {}
    for name, fn in PHASES:
        t0 = time.perf_counter()
        compiled0, hits0 = counter.compiled, counter.cache_hits
        try:
            info = dict(fn(sz))
            if "not_run" not in info:        # not run, for a stated reason
                info = {"ok": True, **info}
        except Exception:  # noqa: BLE001 — a failed phase is a result
            traceback.print_exc()
            info = {"ok": False, "error":
                    traceback.format_exc().strip().splitlines()[-1][:2000]}
        info["seconds"] = round(time.perf_counter() - t0, 1)
        info["compiled"] = counter.compiled - compiled0
        info["cache_hits"] = counter.cache_hits - hits0
        phases[name] = info
        status = ("not_run" if "not_run" in info
                  else "ok" if info["ok"] else "FAILED")
        print(f"chip_smoke: phase {name}: {status} {json.dumps(info)}",
              flush=True)
        gc.collect()

    ok = all(p.get("ok", True) for p in phases.values())
    summary = {
        "ok": bool(ok),
        "device": device,
        "tiny": sz.tiny,
        "phases": phases,
        "compile": {"cache_dir": cache_dir, "compiled": counter.compiled,
                    "cache_hits": counter.cache_hits},
        "wall_seconds": round(time.perf_counter() - t_start, 1),
        "claim": None,
    }
    print(json.dumps(summary), flush=True)
    # the last line holds exactly these keys; the summary is the line above
    print(json.dumps({"ok": bool(ok), "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
