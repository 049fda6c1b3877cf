"""Continuous-batching decode engine over the paged KV cache.

Closes the gap between "has a KV cache" and a serving story for the GPT
family (the reference framework is training-only; this is a TPU-native
extension).  Design:

* a fixed set of **slots** (the decode batch dimension, static forever);
* ONE jitted decode step for the whole engine lifetime — per-slot
  positions, the paged block tables, and the active mask are ordinary
  array arguments, so requests joining/leaving/preempting never touch
  the compiler;
* **bucketed dense prefill**: a new request's prompt runs through the
  dense causal forward (matmul-heavy, MXU-friendly — NOT T incremental
  steps) padded to a small set of bucket lengths, writing K/V for all
  positions at once.  Right padding is exact under causal masking: real
  positions never attend to pad.  One compile per bucket, ever;
* **on-demand block allocation**: a slot holds only the blocks its
  tokens actually fill.  When the pool runs dry the youngest slot is
  preempted back to the queue (its blocks freed) and replayed later —
  deterministic under greedy decoding;
* host scheduler does admission (FCFS), harvest (EOS / max_new), and
  bookkeeping in numpy; the device only ever sees static shapes.

The per-request oracle is ``models.gpt.generate`` — the engine must
produce exactly the tokens the plain whole-batch decoder produces
(tests/test_serving.py).
"""
import collections
import dataclasses
import functools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import trace as _trace
from ..chaos import point as _chaos_point
from ..models import gpt as G
from ..models.gpt import GPTConfig
from ..monitor import get_monitor
from .cache import (init_paged_pools, lookup_blocks, pool_attend,
                    pool_attend_queries, pool_write_at,
                    pool_write_prompt_batch, pool_write_token)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new: int
    eos: Optional[int] = None
    # 0.0 = greedy; > 0 samples with a PER-REQUEST key discipline
    # (fold_in(base, uid) then fold_in per token index), so a sampled
    # request's tokens are identical whatever slot it lands in, whatever
    # else is in flight, and across preemption replays — unlike a
    # batch-level rng, where scheduling would change the output
    temperature: float = 0.0
    # top_k > 0: sample only among the k highest logits (ties at the
    # k-th logit are all kept); top_p < 1: nucleus sampling — the
    # smallest set of tokens whose cumulative probability reaches p.
    # Both filters are deterministic functions of the logits, so the
    # scheduling-invariance of the key discipline carries over intact.
    # Ignored when temperature == 0 (greedy).
    top_k: int = 0
    top_p: float = 1.0
    # when the request entered the system (perf_counter clock); the
    # front-end stamps it at construction, submit() back-fills, and a
    # preemption re-stamps on requeue — queue-wait observability
    # (kungfu_tpu_serving_queue_wait_seconds) measures the CURRENT wait,
    # not wait-plus-discarded-compute
    arrival_t: Optional[float] = None
    # the ORIGINAL arrival, never re-stamped: total sojourn (e2e SLO,
    # journal TTFT) stays recoverable across preemption requeues, while
    # arrival_t above keeps measuring the current wait
    first_arrival_t: Optional[float] = None


@dataclasses.dataclass
class _Running:
    req: Request
    slot: int
    blocks: List[int]            # pool blocks owned, in logical order
    out: List[int]               # generated tokens so far
    # speculative drafting: incremental bigram -> most recent STRICTLY
    # EARLIER position of its second token.  A bigram ending at position
    # i is only indexed once token i+1 exists, so looking up the
    # history's tail always returns a previous occurrence — O(1) per
    # emitted token instead of _propose_draft's O(history) rescan
    ngrams: Dict[tuple, int] = dataclasses.field(default_factory=dict)
    indexed_to: int = 0          # history prefix length already indexed

    def history(self) -> List[int]:
        return list(self.req.prompt) + self.out

    def index_history(self) -> None:
        """Advance the bigram index to cover history[:-1] (the tail
        bigram stays unindexed until the next token arrives)."""
        h = self.history()
        start = max(self.indexed_to, 2)
        for i in range(start, len(h)):
            # token i exists, so bigram ending at i-1 is now "earlier"
            self.ngrams[(h[i - 2], h[i - 1])] = i - 1
        self.indexed_to = max(self.indexed_to, len(h))

    def draft(self, K: int) -> List[int]:
        """Prompt-lookup draft via the incremental index; equivalent to
        _propose_draft(history, K) (asserted in tests)."""
        h = self.history()
        if len(h) < 3 or K <= 0:
            return []
        self.index_history()
        p = self.ngrams.get((h[-2], h[-1]))
        if p is None:
            return []
        return h[p + 1:p + 1 + K]


class EngineStats:
    def __init__(self, slots: int = 0):
        self._slots = slots
        self.reset()

    def reset(self):
        """Zero the counters (e.g. after a warm-up run); keeps the slot
        count the occupancy metric divides by."""
        self.decode_steps = 0        # position budget (K or Q per go)
        self.dispatches = 0          # device programs launched (decode)
        self.slot_steps = 0          # sum over steps of active slots
        self.tokens_out = 0          # tokens DELIVERED (preempted work
        self.prefills = 0            # is subtracted when discarded)
        self.preemptions = 0
        self.spec_proposed = 0       # speculative: drafted tokens sent
        self.spec_accepted = 0       # ...and verified == model argmax
        self.prefix_hits = 0         # admissions served from the cache
        self.prefix_tokens_reused = 0  # prompt tokens NOT recomputed
        self.wall_s = 0.0

    @property
    def occupancy(self):
        tot = self.decode_steps * self._slots if self.decode_steps else 0
        return self.slot_steps / tot if tot else 0.0

    def summary(self):
        out = {"tokens_out": self.tokens_out,
               "decode_steps": self.decode_steps,
               "dispatches": self.dispatches,
               "prefills": self.prefills,
               "preemptions": self.preemptions,
               "occupancy": round(self.occupancy, 3),
               "wall_s": round(self.wall_s, 3),
               "tok_per_s": round(self.tokens_out / self.wall_s, 1)
               if self.wall_s else 0.0}
        if self.prefix_hits:
            out["prefix_hits"] = self.prefix_hits
            out["prefix_tokens_reused"] = self.prefix_tokens_reused
        if self.spec_proposed:
            out["spec_proposed"] = self.spec_proposed
            out["spec_accepted"] = self.spec_accepted
            out["spec_accept_rate"] = round(
                self.spec_accepted / self.spec_proposed, 3)
        return out


def _decode_core(params, cfg: GPTConfig, block_size: int, pools, tables,
                 pos, tokens, attend_mode: str = "auto", tp_axis=None):
    """One decode step for every slot: feed each its last token at its
    own position, scatter K/V through the block tables, return logits.
    Inactive slots have zeroed table rows, so their writes land in the
    scratch block — no conditionals anywhere.  The attend reads straight
    off the pool: the Pallas paged-attention kernel on TPU, the portable
    gather path elsewhere (cache.paged_attend).  Under ``tp_axis`` the
    pools hold each rank's KV-head shard and per-layer psums restore
    replicated activations — the same Megatron sharding as training."""
    x = G.embed(params, tokens[:, None], pos[:, None], cfg)
    blk, off = lookup_blocks(tables, pos, block_size)
    new_pools = []
    for layer, pool in zip(params["layers"], pools):
        q, kk, v = G._layer_qkv(layer, x, cfg, pos=pos[:, None])
        pool = pool_write_token(pool, blk, off, kk[:, 0], v[:, 0])
        new_pools.append(pool)
        o = pool_attend(q, pool, tables, pos, mode=attend_mode)
        x = G._layer_finish(layer, x, o, cfg, tp_axis)
    x = G.rms_norm(x, params["lnf"], cfg.norm_eps)
    return G.tp_head(params, x, tp_axis), new_pools    # [S, V] f32


def _filter_logits(lg, k, p):
    """Top-k / top-p (nucleus) filter for one logits row [V] (f32):
    tokens outside the filter go to -inf.  ``k <= 0`` and ``p >= 1``
    disable their halves.  Ties at the k-th logit are all kept; top-p
    keeps the smallest descending-probability prefix whose cumulative
    mass reaches p (always at least the argmax).  Pure function of
    (logits, k, p) — scheduling-invariance is preserved."""
    V = lg.shape[-1]
    srt = jnp.sort(lg)[::-1]                        # descending
    kk = jnp.clip(jnp.where(k <= 0, V, k), 1, V)
    kth = srt[kk - 1]
    probs = jax.nn.softmax(srt)
    cum = jnp.cumsum(probs) - probs                 # exclusive prefix mass
    n_keep = jnp.sum(cum < p)                       # >= 1 for p > 0
    pth = srt[jnp.maximum(n_keep - 1, 0)]
    return jnp.where(lg >= jnp.maximum(kth, pth), lg, -jnp.inf)


def _pick_tokens(logits, uid_lo, uid_hi, tcount, temp, top_k, top_p):
    """Greedy or per-slot sampled next token.  The sampling key depends
    ONLY on (request uid — both 32-bit halves — and token index):
    scheduling-invariant.  top_k/top_p filter the logits per slot
    before the draw (deterministically, so the invariance holds).  The
    discarded sampling work on greedy slots is a [V] sort + Gumbel
    draws per slot — small next to the [S, V] lm_head matmul that
    produced the logits, so one executable serves both modes."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sample_one(lg, lo, hi, t, tau, k, p):
        key = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(0), lo), hi), t)
        lg = _filter_logits(lg.astype(jnp.float32), k, p)
        return jax.random.categorical(key, lg / jnp.maximum(tau, 1e-6))

    sampled = jax.vmap(sample_one)(logits, uid_lo, uid_hi, tcount,
                                   temp, top_k, top_p).astype(jnp.int32)
    return jnp.where(temp > 0, sampled, greedy)


def _pool_specs(tp_axis, quant: bool, n_layers: int):
    """PartitionSpec tree for the pools: KV heads sharded over tp (each
    rank holds its head shard's blocks); int8 pools add 3-D scale planes
    sharded the same way."""
    p4 = P(None, None, tp_axis, None)
    if not quant:
        return [{"k": p4, "v": p4}] * n_layers
    p3 = P(None, None, tp_axis)
    return [{"k": p4, "ks": p3, "v": p4, "vs": p3}] * n_layers


def _make_decode_chunk(cfg: GPTConfig, block_size: int, chunk: int,
                       attend_mode: str = "auto", mesh=None,
                       tp_axis: str = "tp", quant: bool = False,
                       prep=None, pspecs=None):
    """``chunk`` decode steps in ONE device program (a lax.scan feeding
    each sampled token to the next step on-device), returning all sampled
    tokens [chunk, S] at once.

    A host round trip per TOKEN (sync the sampled id, re-upload
    positions) leaves the device idle for the length of the trip
    between every two decode steps.  One round trip per ``chunk``
    tokens amortizes it; the cost is slot-churn
    granularity (a finished sequence's slot refills at the next chunk
    boundary, and its trailing in-chunk steps sample discarded garbage —
    bounded by chunk-1 slot-steps per finish, all safely routed to the
    slot's own blocks or scratch).

    With ``mesh``, the whole chunk runs shard_mapped over its tp axis:
    params Megatron-sharded (G.param_specs), pools KV-head-sharded,
    tables/positions replicated.  Every rank all-gathers identical
    logits and samples the same token, so the host scheduler is
    unchanged."""

    def run(params, pools, tables, pos, tokens, uid_lo, uid_hi, tcount,
            temp, top_k, top_p, tp_axis_=None):
        if tp_axis_ is not None:
            # the token carry becomes tp-varying after the first gathered
            # sample; align the initial carry's varying-state with that
            tokens = lax.pcast(tokens, (tp_axis_,), to="varying")

        def body(carry, _):
            pools, pos, tok, tc = carry
            p = params
            if prep is not None:
                # dequant INSIDE the scan body, pinned to the
                # loop-varying step counter: XLA's while-loop LICM
                # would otherwise hoist the convert out of the scan and
                # materialize a full-dtype weight copy — paying an
                # extra write+read per chunk and forfeiting the halved
                # per-step weight stream that is the whole point
                # (measured 0.94x before pinning).  The barrier ties
                # the int8 leaves to ``tc`` so the dequant stays
                # per-step and fuses into each dot's weight read.
                leaves, tdef = jax.tree_util.tree_flatten(params)
                pinned = lax.optimization_barrier(tuple(leaves) + (tc,))
                p = prep(jax.tree_util.tree_unflatten(tdef,
                                                      pinned[:-1]))
            logits, pools = _decode_core(p, cfg, block_size, pools,
                                         tables, pos, tok, attend_mode,
                                         tp_axis_)
            nxt = _pick_tokens(logits, uid_lo, uid_hi, tc, temp,
                               top_k, top_p)
            return (pools, pos + 1, nxt, tc + 1), nxt

        (pools, _, _, _), toks = lax.scan(
            body, (pools, pos, tokens, tcount), None, length=chunk)
        if tp_axis_ is not None:
            # ranks computed identical tokens; pmax is an identity that
            # PROVES replication so the P() out_spec type-checks
            toks = lax.pmax(toks, tp_axis_)
        return toks, pools                          # toks [chunk, S]

    if mesh is None:
        return jax.jit(run, donate_argnums=(1,))
    specs = pspecs if pspecs is not None else G.param_specs(cfg, tp_axis)
    rep = P()
    body = functools.partial(run, tp_axis_=tp_axis)
    sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(specs, _pool_specs(tp_axis, quant, cfg.n_layers),
                  rep, rep, rep, rep, rep, rep, rep, rep, rep),
        out_specs=(rep, _pool_specs(tp_axis, quant, cfg.n_layers)))
    return jax.jit(sm, donate_argnums=(1,))


def _make_verify(cfg: GPTConfig, block_size: int, K: int,
                 attend_mode: str = "auto", mesh=None,
                 tp_axis: str = "tp", quant: bool = False, prep=None,
                 pspecs=None):
    """Speculative-decoding verify step: feed every slot its current
    token PLUS ``K`` drafted continuations (Q = K+1 query positions) in
    ONE forward, return the model's prediction at each position.

    Decode attention is HBM-bandwidth-bound: sweeping the cache once for
    Q queries costs barely more than for one, so drafted tokens that
    match the model's own argmax are verified almost for free — greedy
    speculative decoding is LOSSLESS (the emitted stream is exactly the
    sequential argmax stream, whatever the drafts were; only throughput
    changes with draft quality).

    Rejected positions leave stale K/V in the pool; that is safe by
    construction: a query at position p only attends keys <= p, and
    every position <= the next step's highest used query is re-written
    by that step before its attends run."""
    Q = K + 1

    def verify(params, pools, tables, pos, draft, uid_lo, uid_hi,
               tcount, temp, top_k, top_p, tp_axis_=None):
        if prep is not None:
            params = prep(params)
        qpos = pos[:, None] + jnp.arange(Q)[None, :]      # [S, Q]
        x = G.embed(params, draft, qpos, cfg)             # [S, Q, D]
        new_pools = []
        for layer, pool in zip(params["layers"], pools):
            q, kk, v = G._layer_qkv(layer, x, cfg, pos=qpos)
            pool = pool_write_at(pool, tables, qpos, kk, v, block_size)
            new_pools.append(pool)
            # one cache sweep for all Q queries (per-query causal mask)
            o = pool_attend_queries(q, pool, tables, qpos,
                                    mode=attend_mode)     # [S, Q, H, Dh]
            x = G._layer_finish(layer, x, o, cfg, tp_axis_)
        x = G.rms_norm(x, params["lnf"], cfg.norm_eps)
        S = x.shape[0]
        # G.tp_head is the ONE tp-logits implementation (vocab-gather
        # convention lives there); fold Q into the batch to reuse it
        logits = G.tp_head(params, x.reshape(S * Q, 1, x.shape[-1]),
                           tp_axis_).reshape(S, Q, -1)    # [S, Q, V]
        preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # position 0 honors the per-request sampling discipline (spec
        # drafts are greedy-only; sampled slots run with dlen = 0, so
        # only their column 0 is ever consumed)
        preds = preds.at[:, 0].set(
            _pick_tokens(logits[:, 0], uid_lo, uid_hi, tcount, temp,
                         top_k, top_p))
        if tp_axis_ is not None:
            preds = lax.pmax(preds, tp_axis_)  # identity: proves replication
        return preds, new_pools                           # preds [S, Q]

    if mesh is None:
        return jax.jit(verify, donate_argnums=(1,))
    specs = pspecs if pspecs is not None else G.param_specs(cfg, tp_axis)
    rep = P()
    body = functools.partial(verify, tp_axis_=tp_axis)
    sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(specs, _pool_specs(tp_axis, quant, cfg.n_layers),
                  rep, rep, rep, rep, rep, rep, rep, rep, rep),
        out_specs=(rep, _pool_specs(tp_axis, quant, cfg.n_layers)))
    return jax.jit(sm, donate_argnums=(1,))


def _propose_draft(history, K: int, ngram: int = 2):
    """Prompt-lookup drafting: find the most recent earlier occurrence
    of the trailing ``ngram`` tokens in ``history`` and propose the K
    tokens that followed it.  Returns [] when no match — the verify
    step then just decodes one token (never worse than plain decode).
    Pure host-side; the model never sees a draft it didn't verify."""
    n = len(history)
    if n < ngram + 1:
        return []
    tail = history[-ngram:]
    # search backward, excluding the trailing occurrence itself
    for start in range(n - ngram - 1, -1, -1):
        if history[start:start + ngram] == tail:
            nxt = history[start + ngram:start + ngram + K]
            if nxt:
                return list(nxt)
    return []


def _make_prefill(cfg: GPTConfig, block_size: int, group: int,
                  mesh=None, tp_axis: str = "tp", quant: bool = False,
                  prep=None, pspecs=None):
    """Bucketed dense prefill for a GROUP of requests in one device
    program: causal forward over the padded prompts (one matmul-heavy
    pass — the MXU path, not T scan steps), K/V scattered into every
    group member's blocks at once, greedy first token from each row's
    hidden state at its true last position.

    ``group`` is static (the admission batch is padded up to it with
    ``t_real = 0`` rows whose writes all route to scratch); ``t_real``
    [group] is traced, so every prompt-length mix in a bucket shares the
    compile.  Batching admissions matters for the same reason chunked
    decode does: admitting N requests must not cost N dispatches, each
    with its own host round trip."""

    def prefill(params, pools, table_rows, tokens, t_real, uid_lo,
                uid_hi, temp, top_k, top_p, tp_axis_=None):
        if prep is not None:
            params = prep(params)
        T = tokens.shape[1]                              # [G, T]
        pos = jnp.arange(T)
        x = G.embed(params, tokens, pos, cfg)            # [G, T, D]
        new_pools = []
        for layer, pool in zip(params["layers"], pools):
            q, kk, v = G._layer_qkv(layer, x, cfg, pos=pos)
            pool = pool_write_prompt_batch(pool, table_rows, kk, v,
                                           t_real, block_size)
            new_pools.append(pool)
            # local head shard attends (GQA group ratio is tp-invariant);
            # the psum in _layer_finish restores replicated activations
            o = G._attend(q, kk, v, "dense", None, kv_groups=cfg.kv_groups)
            x = G._layer_finish(layer, x, o, cfg, tp_axis_)
        x = G.rms_norm(x, params["lnf"], cfg.norm_eps)
        h_last = jnp.take_along_axis(
            x, jnp.maximum(t_real - 1, 0)[:, None, None], axis=1)
        logits = G.tp_head(params, h_last, tp_axis_)     # [G, V]
        tok0 = _pick_tokens(logits, uid_lo, uid_hi,
                            jnp.zeros_like(uid_lo), temp, top_k, top_p)
        if tp_axis_ is not None:
            tok0 = lax.pmax(tok0, tp_axis_)   # identity; proves replication
        return tok0, new_pools

    if mesh is None:
        return jax.jit(prefill, donate_argnums=(1,))
    specs = pspecs if pspecs is not None else G.param_specs(cfg, tp_axis)
    rep = P()
    body = functools.partial(prefill, tp_axis_=tp_axis)
    sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(specs, _pool_specs(tp_axis, quant, cfg.n_layers),
                  rep, rep, rep, rep, rep, rep, rep, rep),
        out_specs=(rep, _pool_specs(tp_axis, quant, cfg.n_layers)))
    return jax.jit(sm, donate_argnums=(1,))


def _make_prefill_cached(cfg: GPTConfig, block_size: int, group: int,
                         mesh=None, tp_axis: str = "tp", prep=None,
                         pspecs=None):
    """Suffix prefill for prefix-cache hits: each row's prompt SUFFIX
    (positions ``t_cached .. t_cached + t_real - 1``) runs the dense
    forward; its K/V scatter to the row's own blocks at those absolute
    positions, and the attend reads the whole cache through the block
    tables — the shared prefix blocks (written by an earlier request)
    plus the just-written suffix, one gathered pass per layer.  The
    compute saved is the whole prefix's QKV/FFN/attention — the point
    of prefix caching.  Non-quantized pools only: the pool stores K/V
    in the model dtype, so a cached prefix is bit-identical to a
    recomputed one (int8 would substitute dequantized values where the
    uncached prefill attends fresh ones)."""

    def prefill(params, pools, table_rows, tokens, t_real, t_cached,
                uid_lo, uid_hi, temp, top_k, top_p, tp_axis_=None):
        if prep is not None:
            params = prep(params)
        T = tokens.shape[1]                              # [G, T] suffixes
        rel = jnp.arange(T)
        qpos = t_cached[:, None] + rel[None, :]          # absolute [G, T]
        x = G.embed(params, tokens, qpos, cfg)
        limit = table_rows.shape[1] * block_size
        # pad positions (rel >= t_real) route to scratch — their qpos
        # points INTO allocated blocks, so an unmasked write would
        # corrupt live cache with pad garbage
        wpos = jnp.where(rel[None, :] < t_real[:, None], qpos, limit)
        new_pools = []
        for layer, pool in zip(params["layers"], pools):
            q, kk, v = G._layer_qkv(layer, x, cfg, pos=qpos)
            pool = pool_write_at(pool, table_rows, wpos, kk, v,
                                 block_size)
            new_pools.append(pool)
            # one gathered sweep serves prefix + fresh suffix (the
            # suffix was just written); per-query causal mask comes
            # from the absolute positions
            o = pool_attend_queries(q, pool, table_rows, qpos,
                                    mode="gather")
            x = G._layer_finish(layer, x, o, cfg, tp_axis_)
        x = G.rms_norm(x, params["lnf"], cfg.norm_eps)
        h_last = jnp.take_along_axis(
            x, jnp.maximum(t_real - 1, 0)[:, None, None], axis=1)
        logits = G.tp_head(params, h_last, tp_axis_)     # [G, V]
        tok0 = _pick_tokens(logits, uid_lo, uid_hi,
                            jnp.zeros_like(uid_lo), temp, top_k, top_p)
        if tp_axis_ is not None:
            tok0 = lax.pmax(tok0, tp_axis_)   # identity; proves replication
        return tok0, new_pools

    if mesh is None:
        return jax.jit(prefill, donate_argnums=(1,))
    specs = pspecs if pspecs is not None else G.param_specs(cfg, tp_axis)
    rep = P()
    body = functools.partial(prefill, tp_axis_=tp_axis)
    sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(specs, _pool_specs(tp_axis, False, cfg.n_layers),
                  rep, rep, rep, rep, rep, rep, rep, rep, rep),
        out_specs=(rep, _pool_specs(tp_axis, False, cfg.n_layers)))
    return jax.jit(sm, donate_argnums=(1,))


class DecodeEngine:
    """Continuous-batching serving loop.

    ``num_blocks`` * ``block_size`` tokens of KV cache are shared by all
    slots; ``max_len`` bounds any single sequence (its table width).
    ``prompt_buckets`` are the static prefill lengths (ascending).
    ``decode_chunk`` tokens are decoded per host round trip (see
    _make_decode_chunk; the trade is slot-churn granularity, so shrink
    it for latency-sensitive serving).
    ``attend`` picks the per-layer cache read: "fused" = the Pallas
    paged-attention kernel (pool bytes DMA'd once, no gathered copy),
    "gather" = portable materialise-then-attend, "auto" = fused on TPU.
    ``mesh`` switches on tensor-parallel serving: decode and prefill run
    shard_mapped over the mesh's ``tp_axis`` with params Megatron-sharded
    and the KV pools sharded by KV head; a host params tree is sharded
    automatically.  The host scheduler is identical — every rank
    all-gathers the same logits and picks the same token, so block
    tables, admission, preemption, and replay don't know tp exists.
    ``kv_dtype=jnp.int8`` stores the cache quantized (one f32 scale per
    token per KV head, dequantized inside the attend): half the pool
    bytes of bf16 — so ~2x the cached tokens per HBM byte and half the
    bandwidth the decode attend sweeps — at a small accuracy cost.
    Quantization is deterministic, so preemption replay stays exact.
    ``speculative=K`` switches the decode loop to speculative decoding
    with prompt-lookup drafting: each dispatch verifies the current
    token + up to K drafted continuations in one bandwidth-bound pass
    and emits the matching prefix + the model's own next token — up to
    K+1 tokens per dispatch, **lossless for greedy** (the stream equals
    sequential argmax whatever the drafts), and sampled requests fall
    back to 1-token steps with the usual key discipline.  Replaces
    ``decode_chunk`` (drafts come from the host between dispatches).
    ``prefix_cache=True`` shares prompt-prefix KV across requests:
    full blocks are keyed by their token prefix with refcounts; an
    admission whose prefix is cached prefills only its SUFFIX (the
    dense compute for the shared prefix is skipped entirely — the win
    for system-prompt / few-shot workloads), reading the shared blocks
    through its table.  Unreferenced cached blocks form an LRU the
    allocator evicts under pressure.  A preempted request pins its
    prefix split so the replay is numerically identical (streamed
    tokens never roll back).  Requests admitted in one batched prefill
    cannot share with each other (entries land after the prefill);
    model-dtype pools only.
    """

    def __init__(self, params, cfg: GPTConfig, *, num_slots: int = 8,
                 block_size: int = 32, num_blocks: int = 64,
                 max_len: Optional[int] = None,
                 prompt_buckets=(32, 128, 512), decode_chunk: int = 8,
                 prefill_group: Optional[int] = None, on_tokens=None,
                 attend: str = "auto", mesh=None, tp_axis: str = "tp",
                 kv_dtype=None, speculative: int = 0,
                 prefix_cache: bool = False,
                 weights_int8: bool = False,
                 weights_int8_min_size: int = 0):
        if attend not in ("auto", "fused", "gather"):
            raise ValueError(f"attend must be auto|fused|gather, "
                             f"got {attend!r}")
        G._plain_layers_only(cfg, "DecodeEngine")
        quant = kv_dtype == jnp.int8
        if kv_dtype is not None and not quant:
            raise ValueError("kv_dtype must be None (model dtype) or "
                             "jnp.int8")
        prep = None
        pspecs = None
        if weights_int8:
            # weight-only int8 (W8A16): halves the per-step HBM weight
            # stream of low-concurrency decode; dequant runs inside
            # each jitted step (ops/quant.py).  Quantization happens on
            # the HOST tree BEFORE any tp sharding, so scales reduce
            # over the full (global) leading axes and shard alongside
            # their weights (quantize_specs).
            # weights_int8_min_size quantizes only leaves of at least
            # that many elements: the per-layer decode dots measure
            # int8-NEUTRAL at d1024 shapes, so throughput-sensitive
            # deployments can restrict quantization to the vocab-sized
            # head (e.g. 10_000_000) — see ops/quant.py's measured
            # breakdown; residency-motivated ones keep the default 0
            from ..ops.quant import dequantize_weights, quantize_weights
            params = quantize_weights(params,
                                      min_size=weights_int8_min_size)
            prep = lambda q: dequantize_weights(q, cfg.dtype)
        if mesh is not None:
            G.validate_tp(cfg,
                          mesh.devices.shape[mesh.axis_names.index(tp_axis)])
            pspecs = G.param_specs(cfg, tp_axis)
            if weights_int8:
                from ..ops.quant import quantize_specs
                pspecs = quantize_specs(params, pspecs)
            # accept a host tree (shard it) or already-sharded params
            params = jax.tree_util.tree_map(
                lambda t, s: jax.device_put(t, NamedSharding(mesh, s)),
                params, pspecs)
        self.mesh = mesh
        self.tp_axis = tp_axis
        self.weights_int8 = bool(weights_int8)
        self.params = params
        self.cfg = cfg
        self.S = num_slots
        self.bs = block_size
        self.max_len = max_len or cfg.max_seq
        if not cfg.rope and self.max_len > cfg.max_seq:
            raise ValueError("max_len beyond wpe table")
        self.max_blocks = -(-self.max_len // block_size)
        self.buckets = tuple(sorted(b for b in prompt_buckets
                                    if b <= self.max_len))
        if not self.buckets:
            raise ValueError("no prompt bucket fits max_len")
        self.pools = init_paged_pools(cfg, num_blocks, block_size,
                                      kv_dtype=kv_dtype)
        if mesh is not None:
            self.pools = jax.tree_util.tree_map(
                lambda t, s: jax.device_put(t, NamedSharding(mesh, s)),
                self.pools, _pool_specs(tp_axis, quant, cfg.n_layers))
        self._total_blocks = num_blocks - 1      # block 0 is scratch
        self._free = collections.deque(range(1, num_blocks))
        # ---- prefix cache: refcounted shared prompt blocks ----
        # a block is in exactly one place: _free (uncached, ref 0),
        # _reclaim (cached, ref 0 — evictable LRU), or referenced by
        # >= 1 running slots (ref > 0, possibly cached).  Cache entries
        # key on the FULL token prefix through that block, so identical
        # prompt prefixes land on the same physical blocks.
        if prefix_cache and quant:
            raise ValueError(
                "prefix_cache requires the model-dtype pool: the int8 "
                "cache would substitute dequantized prefix values where "
                "an uncached prefill attends fresh ones")
        self.prefix_cache = bool(prefix_cache)
        self._block_ref = np.zeros(num_blocks, np.int32)
        self._block_key: Dict[int, tuple] = {}
        self._prefix_index: Dict[tuple, int] = {}
        self._reclaim: "collections.OrderedDict[tuple, int]" = \
            collections.OrderedDict()
        # per-uid admission split (prompt tokens served from cache) and
        # the pinned prefix blocks a preempted uid keeps referenced so
        # its replay re-admits with the SAME split and values —
        # deterministic replay (streamed tokens never roll back)
        self._admit_split: Dict[int, int] = {}
        self._pinned: Dict[int, List[int]] = {}
        # uids whose pins had to be dropped (all-prefix victim under
        # extreme pressure): their replay is forced to t_cached=0 so the
        # split is at least DETERMINISTIC; in bf16 the re-prefilled
        # stream can still diverge from the cached-split original on
        # near-tie argmaxes (documented corner: requires prefix_cache +
        # streaming + a pin-drop preemption)
        self._force_fresh: set = set()
        self._tables = np.zeros((num_slots, self.max_blocks), np.int32)
        self._pos = np.zeros(num_slots, np.int32)
        self._tok = np.zeros(num_slots, np.int32)
        self._uid_lo = np.zeros(num_slots, np.uint32)
        self._uid_hi = np.zeros(num_slots, np.uint32)
        self._tcount = np.zeros(num_slots, np.int32)
        self._temp = np.zeros(num_slots, np.float32)
        self._topk = np.zeros(num_slots, np.int32)
        self._topp = np.ones(num_slots, np.float32)
        self._running: List[Optional[_Running]] = [None] * num_slots
        self._queue: "collections.deque[Request]" = collections.deque()
        # streaming: emit each request's tokens as they are produced.
        # Replay after preemption regenerates BIT-IDENTICAL tokens (both
        # greedy and sampled streams are scheduling-invariant), so
        # _emitted[uid] suppresses re-emission and a consumer never sees
        # a duplicate or a rollback.
        self.on_tokens = on_tokens          # fn(uid, new_tokens) or None
        self._emitted: Dict[int, int] = {}
        self._admit_order: List[int] = []    # slots, oldest first
        self._results: Dict[int, List[int]] = {}
        self.K = max(1, decode_chunk)
        self.G = max(1, min(prefill_group or min(num_slots, 8), num_slots))
        self.spec = max(0, int(speculative))
        if self.spec:
            self._verify = _make_verify(cfg, block_size, self.spec,
                                        attend, mesh, tp_axis, quant,
                                        prep=prep, pspecs=pspecs)
        else:
            self._decode = _make_decode_chunk(cfg, block_size, self.K,
                                              attend, mesh, tp_axis,
                                              quant, prep=prep,
                                              pspecs=pspecs)
        self._prefill = _make_prefill(cfg, block_size, self.G, mesh,
                                      tp_axis, quant, prep=prep,
                                      pspecs=pspecs)
        if self.prefix_cache:
            self._prefill_cached = _make_prefill_cached(
                cfg, block_size, self.G, mesh, tp_axis, prep=prep,
                pspecs=pspecs)
        self.stats = EngineStats(num_slots)
        # serving latency observability (docs/monitoring.md): admission
        # wall clock per in-flight uid (request span = admit -> harvest)
        # and lifetime denominators for the prefix-cache gauges
        self._admit_t: Dict[int, float] = {}
        self._admitted_total = 0
        self._prompt_tokens_total = 0
        # per-request lifecycle journal + SLO plane (serving/slo.py):
        # arrival/admit/first-token/finish, preemption counts, prefix
        # reuse — feeds /requests, the kungfu_tpu_slo_* gauges, and the
        # kfrequests JSONL stream trace/merge.py folds into the timeline
        from .slo import RequestJournal
        self.journal = RequestJournal()
        # kfprof step attribution for the decode loop: compute = prefill
        # + decode dispatch->sync, host = scheduler remainder
        from ..monitor.profiler import StepPhases
        self._prof_phases = StepPhases(loop="serve")

    # ------------------------------------------------------------- admin
    def validate_shape(self, req: Request) -> None:
        """Static admissibility checks (no engine state touched — safe
        to call from any thread, e.g. an HTTP handler pre-validating
        before handing the request to the scheduler thread)."""
        if not req.prompt or req.max_new < 1:
            raise ValueError(f"request {req.uid}: needs a non-empty "
                             f"prompt and max_new >= 1")
        need = len(req.prompt) + req.max_new
        if need > self.max_len:
            raise ValueError(f"request {req.uid}: prompt+max_new {need} "
                             f"exceeds max_len {self.max_len}")
        if -(-need // self.bs) > self._total_blocks:
            raise ValueError(f"request {req.uid}: needs more KV blocks "
                             f"than the whole pool holds")
        if len(req.prompt) > self.buckets[-1]:
            raise ValueError(f"request {req.uid}: prompt longer than the "
                             f"largest prefill bucket {self.buckets[-1]}")
        if not (0.0 < req.top_p <= 1.0):
            raise ValueError(f"request {req.uid}: top_p must be in "
                             f"(0, 1], got {req.top_p}")
        if req.top_k < 0:
            raise ValueError(f"request {req.uid}: top_k must be >= 0, "
                             f"got {req.top_k}")

    def submit(self, req: Request) -> None:
        self.validate_shape(req)
        in_flight = ({r.uid for r in self._queue}
                     | {r.req.uid for r in self._running if r is not None}
                     | set(self._results))
        if req.uid in in_flight:
            raise ValueError(f"request uid {req.uid} already in flight "
                             f"(uids key both results and sampling)")
        if req.arrival_t is None:
            req.arrival_t = time.perf_counter()
        if req.first_arrival_t is None:
            req.first_arrival_t = req.arrival_t
        self.journal.on_submit(req.uid, req.first_arrival_t,
                               len(req.prompt))
        self._queue.append(req)

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise AssertionError  # submit() validated

    def _available(self) -> int:
        return len(self._free) + len(self._reclaim)

    def _alloc(self, n: int) -> Optional[List[int]]:
        if self._available() < n:
            return None
        while len(self._free) < n:
            # evict the least-recently-freed cached block (its cache
            # entry dies; the block itself is reused)
            key, blk = self._reclaim.popitem(last=False)
            self._prefix_index.pop(key, None)
            self._block_key.pop(blk, None)
            self._free.append(blk)
        out = [self._free.popleft() for _ in range(n)]
        for b in out:
            self._block_ref[b] = 1
        return out

    def _acquire_shared(self, blk: int) -> None:
        """Take a reference on a cached block (reviving it from the
        reclaim list if no running slot holds it)."""
        if self._block_ref[blk] == 0:
            key = self._block_key.get(blk)
            if key is not None:
                self._reclaim.pop(key, None)
        self._block_ref[blk] += 1

    def _release_block(self, blk: int) -> None:
        self._block_ref[blk] -= 1
        assert self._block_ref[blk] >= 0
        if self._block_ref[blk] == 0:
            key = self._block_key.get(blk)
            if key is not None:
                self._reclaim[key] = blk     # cached: evictable, LRU
                self._reclaim.move_to_end(key)
            else:
                self._free.append(blk)

    @staticmethod
    def _chain_keys(prompt, bs, n_blocks):
        """Chained blake2b digests of the prompt's full blocks: key_j
        commits to ALL tokens through block j at O(bs) per block (a
        tuple(prompt[:j*bs]) key would cost O(prefix^2) per probe and
        hash 100k+ ints per admission at benchmark shapes).  16-byte
        digests make collisions negligible; a collision would be a
        correctness bug (wrong KV served), hence a real hash, not
        Python's."""
        import hashlib
        key = b"kft-prefix"
        for j in range(n_blocks):
            h = hashlib.blake2b(key, digest_size=16)
            h.update(np.asarray(prompt[j * bs:(j + 1) * bs],
                                np.int64).tobytes())
            key = h.digest()
            yield key

    def _probe_prefix(self, req: Request):
        """(shared_blocks, t_cached) for this request under the cache.

        A replayed (previously preempted) uid reuses its pinned split
        verbatim — same physical prefix blocks, same t_cached — so the
        re-prefill is numerically identical to the original and the
        already-streamed tokens stay valid.  Fresh requests probe the
        longest contiguous run of cached full blocks, capped one token
        short of the prompt (the prefill needs >= 1 query position to
        produce the first token)."""
        if not self.prefix_cache or req.uid in self._force_fresh:
            return [], 0
        uid = req.uid
        if uid in self._pinned:
            shared = self._pinned[uid]
            return shared, self._admit_split.get(uid, 0)
        p = req.prompt
        shared = []
        n_full = (len(p) - 1) // self.bs  # cap: >= 1 suffix token
        for key in self._chain_keys(p, self.bs, n_full):
            blk = self._prefix_index.get(key)
            if blk is None:
                break
            shared.append(blk)
        return shared, len(shared) * self.bs

    def _cache_insert(self, req: Request, blocks: List[int]) -> None:
        """Register this prompt's full blocks in the prefix index (the
        first sharer's physical blocks win; later identical prompts just
        keep their own copies uncached)."""
        if not self.prefix_cache:
            return
        p = req.prompt
        for j, key in enumerate(self._chain_keys(p, self.bs,
                                                 len(p) // self.bs)):
            if key in self._prefix_index:
                continue
            blk = blocks[j]
            if blk in self._block_key:   # already caches another key
                continue
            self._prefix_index[key] = blk
            self._block_key[blk] = key

    def _free_slot(self, slot: int, keep: int = 0) -> None:
        run = self._running[slot]
        for b in run.blocks[keep:]:
            self._release_block(b)
        self._running[slot] = None
        self._tables[slot] = 0
        self._pos[slot] = 0
        self._tok[slot] = 0
        self._uid_lo[slot] = 0
        self._uid_hi[slot] = 0
        self._tcount[slot] = 0
        self._temp[slot] = 0.0      # freed slots sample nothing (greedy)
        self._topk[slot] = 0
        self._topp[slot] = 1.0
        self._admit_order.remove(slot)

    def _admit(self) -> None:
        """Admit the longest FCFS prefix of the queue that shares one
        prompt bucket and fits (free slot + blocks + growth headroom),
        up to ``prefill_group`` requests — then prefill them all in ONE
        device program.

        Admission hysteresis: while anything is running, wait until
        ``min(prefill_group, queue)`` slots are free before dispatching,
        so freed slots accumulate into one full-group prefill instead of
        one dispatch each (slots free a few per chunk boundary; on a
        high-dispatch-latency backend per-slot admission dominated the
        whole run — measured 51 prefill dispatches for 96 requests)."""
        free_slots = sum(r is None for r in self._running)
        # cap the threshold at S-1: a threshold of S would wait for EVERY
        # running sequence to finish (gang scheduling — exactly the
        # static-batching behavior the engine exists to beat)
        if self._admit_order and free_slots < min(self.G,
                                                  len(self._queue),
                                                  self.S - 1):
            return
        while self._queue:
            # the head's bucket sets the batch shape; later queue entries
            # of the SAME bucket may join it (bounded skip-ahead — the
            # head is always admitted first, so nothing starves).  With
            # strict same-bucket prefixes, mixed workloads averaged ~2.4
            # requests per prefill dispatch; skipping ahead fills groups
            # bucket by the SUFFIX still to compute (the cached prefix
            # skips the prefill entirely — the point of prefix caching)
            head_probe = self._probe_prefix(self._queue[0])
            bucket = self._bucket(len(self._queue[0].prompt)
                                  - head_probe[1])
            batch = []          # (req, slot, blocks, t_cached)
            picked = []                     # queue indices admitted
            for qi, req in enumerate(self._queue):
                if len(batch) >= self.G:
                    break
                shared, t_cached = (head_probe if qi == 0
                                    else self._probe_prefix(req))
                t_suffix = len(req.prompt) - t_cached
                if self._bucket(t_suffix) != bucket:
                    continue
                taken = {s for _, s, *_ in batch}
                slot = next((i for i in range(self.S)
                             if self._running[i] is None
                             and i not in taken), None)
                if slot is None:
                    break
                need = -(-len(req.prompt) // self.bs) - len(shared)
                # +1 growth headroom: admitting with only exactly the
                # prompt's blocks free would preempt (and waste the
                # prefill) within block_size decode steps under pressure
                if self._available() < need + 1 and (self._admit_order
                                                     or batch):
                    break
                pinned = req.uid in self._pinned
                if not pinned:
                    # take refs BEFORE _alloc: an eviction inside the
                    # alloc must not reclaim a block we are about to use
                    for b in shared:
                        self._acquire_shared(b)
                own = self._alloc(need)
                if own is None:
                    if not pinned:
                        for b in shared:
                            self._release_block(b)
                    break
                self._pinned.pop(req.uid, None)
                batch.append((req, slot, shared + own, t_cached))
                picked.append(qi)
            if not batch:
                return
            for qi in reversed(picked):
                del self._queue[qi]
            Tb = bucket
            toks = np.zeros((self.G, Tb), np.int32)
            rows = np.zeros((self.G, self.max_blocks), np.int32)
            t_reals = np.zeros(self.G, np.int32)
            t_cacheds = np.zeros(self.G, np.int32)
            uid_lo = np.zeros(self.G, np.uint32)
            uid_hi = np.zeros(self.G, np.uint32)
            temps = np.zeros(self.G, np.float32)
            topks = np.zeros(self.G, np.int32)
            topps = np.ones(self.G, np.float32)
            for g, (req, slot, blocks, t_cached) in enumerate(batch):
                suffix = req.prompt[t_cached:]
                toks[g, :len(suffix)] = suffix
                rows[g, :len(blocks)] = blocks
                t_reals[g] = len(suffix)
                t_cacheds[g] = t_cached
                uid_lo[g] = req.uid & 0xFFFFFFFF
                uid_hi[g] = (req.uid >> 32) & 0xFFFFFFFF
                temps[g] = req.temperature
                topks[g] = req.top_k
                topps[g] = req.top_p
            # admission fault site: a chaos "delay" here models a slow
            # admission path (SLO burn without touching the device
            # program); "exception" models an admission-plane crash
            _chaos_point("serving.admit", step=self.stats.prefills)
            _t_prefill = time.perf_counter()
            if t_cacheds.any():
                # at least one cached prefix: the suffix program (reads
                # the shared blocks through the tables)
                tok0s, self.pools = self._prefill_cached(
                    self.params, self.pools, jnp.asarray(rows),
                    jnp.asarray(toks), jnp.asarray(t_reals),
                    jnp.asarray(t_cacheds),
                    jnp.asarray(uid_lo), jnp.asarray(uid_hi),
                    jnp.asarray(temps), jnp.asarray(topks),
                    jnp.asarray(topps))
                self.stats.prefix_hits += int((t_cacheds > 0).sum())
                self.stats.prefix_tokens_reused += int(t_cacheds.sum())
            else:
                # all-fresh batch: the original full-prompt program
                # (bit-identical to the cache-off engine)
                tok0s, self.pools = self._prefill(
                    self.params, self.pools, jnp.asarray(rows),
                    jnp.asarray(toks), jnp.asarray(t_reals),
                    jnp.asarray(uid_lo), jnp.asarray(uid_hi),
                    jnp.asarray(temps), jnp.asarray(topks),
                    jnp.asarray(topps))
            tok0s = np.asarray(tok0s)
            self.stats.prefills += 1
            now = time.perf_counter()
            mon = get_monitor()
            mon.observe("kungfu_tpu_serving_prefill_seconds",
                        now - _t_prefill)
            self._prof_phases.add("compute", now - _t_prefill)
            _trace.event("serving.prefill", category="serving",
                         dur=now - _t_prefill,
                         attrs={"batch": len(batch), "bucket": Tb})
            for req, _slot, _blocks, _tc in batch:
                self._admitted_total += 1
                self._prompt_tokens_total += len(req.prompt)
                wait = (now - req.arrival_t
                        if req.arrival_t is not None else 0.0)
                if req.arrival_t is not None:
                    mon.observe("kungfu_tpu_serving_queue_wait_seconds",
                                wait)
                self._admit_t[req.uid] = now
                self.journal.on_admit(req.uid, now, slot=_slot,
                                      prefix_reused=_tc, wait_s=wait)
                # tok0 came out of this prefill: first token lands now
                # (set-once in the journal — a preemption replay's
                # re-prefill does not move it)
                self.journal.on_first_token(req.uid, now)
                if _trace.armed():
                    _trace.event("serving.queue", category="serving",
                                 dur=wait, attrs={"uid": req.uid,
                                                  "slot": _slot})
                    _trace.event("serving.prefill", category="serving",
                                 dur=now - _t_prefill,
                                 attrs={"uid": req.uid, "slot": _slot,
                                        "cached": int(_tc),
                                        "prompt": len(req.prompt)})
            mon.set_gauge("kungfu_tpu_serving_prefix_hit_rate",
                          self.stats.prefix_hits
                          / max(1, self._admitted_total))
            mon.set_gauge("kungfu_tpu_serving_prefix_token_reuse",
                          self.stats.prefix_tokens_reused
                          / max(1, self._prompt_tokens_total))
            for g, (req, slot, blocks, t_cached) in enumerate(batch):
                self._admit_split[req.uid] = t_cached
                self._cache_insert(req, blocks)
                run = _Running(req=req, slot=slot, blocks=blocks, out=[])
                self._tables[slot] = 0
                self._tables[slot, :len(blocks)] = blocks
                tok0 = int(tok0s[g])
                run.out.append(tok0)
                self.stats.tokens_out += 1
                self._running[slot] = run
                self._admit_order.append(slot)
                if self._finished(run):
                    self._harvest(slot)
                    continue
                self._emit(run)
                self._pos[slot] = len(req.prompt)   # next write position
                self._tok[slot] = tok0
                self._uid_lo[slot] = req.uid & 0xFFFFFFFF
                self._uid_hi[slot] = (req.uid >> 32) & 0xFFFFFFFF
                self._tcount[slot] = 1              # tok0 was index 0
                self._temp[slot] = req.temperature
                self._topk[slot] = req.top_k
                self._topp[slot] = req.top_p

    def _finished(self, run: _Running) -> bool:
        return (len(run.out) >= run.req.max_new
                or (run.req.eos is not None and run.out
                    and run.out[-1] == run.req.eos))

    def _emit(self, run: _Running) -> None:
        if self.on_tokens is None:
            return
        seen = self._emitted.get(run.req.uid, 0)
        if len(run.out) > seen:
            self.on_tokens(run.req.uid, run.out[seen:])
            self._emitted[run.req.uid] = len(run.out)

    def _harvest(self, slot: int) -> None:
        run = self._running[slot]
        self._emit(run)
        now = time.perf_counter()
        t_admit = self._admit_t.pop(run.req.uid, None)
        if t_admit is not None:
            # the per-request span (renders as one bar per request in
            # the merged Chrome trace: admit -> last token)
            _trace.event("serving.request", category="serving",
                         dur=now - t_admit,
                         attrs={"uid": run.req.uid,
                                "prompt": len(run.req.prompt),
                                "tokens": len(run.out)})
        rec = self.journal.on_finish(run.req.uid, now,
                                     output_tokens=len(run.out))
        if rec is not None:
            # total queue time across every admission — the re-stamped
            # arrival_t alone cannot reconstruct this (satellite of the
            # queue-wait blind spot; docs/serving.md)
            get_monitor().observe(
                "kungfu_tpu_serving_cumulative_wait_seconds",
                rec.queue_wait_s)
            if _trace.armed():
                _trace.event("serving.finish", category="serving",
                             dur=(now - rec.arrival_t),
                             attrs={"uid": run.req.uid,
                                    "tokens": len(run.out),
                                    "preemptions": rec.preemptions})
        self._emitted.pop(run.req.uid, None)
        self._results[run.req.uid] = run.out
        self._admit_split.pop(run.req.uid, None)
        self._force_fresh.discard(run.req.uid)
        self._free_slot(slot)

    def _preempt_for(self, needy_slot: int) -> bool:
        """Free a slot admitted AFTER the needy one (youngest first); if
        the needy slot is itself the youngest, it preempts ITSELF.  Older
        slots are never the victim, so the oldest request always runs to
        completion — guaranteed progress, and the most-progressed work is
        never the work discarded.  Replays are deterministic under greedy
        decoding.  Returns False only when the needy slot is the sole
        active one (the pool is simply too small)."""
        order = self._admit_order
        younger = order[order.index(needy_slot) + 1:]
        victim = younger[-1] if younger else (
            needy_slot if len(order) > 1 else None)
        if victim is None:
            return False
        run = self._running[victim]
        # re-queued: the CURRENT-wait clock restarts, but
        # req.first_arrival_t (stamped once in submit) is untouched, so
        # total sojourn stays recoverable through the journal
        run.req.arrival_t = time.perf_counter()
        self._admit_t.pop(run.req.uid, None)
        self.journal.on_preempt(run.req.uid)
        get_monitor().inc("kungfu_tpu_serving_preemptions_total",
                          labels={"reason": "kv-pressure"})
        _trace.event("serving.preempt", category="serving",
                     attrs={"uid": run.req.uid, "slot": victim,
                            "reason": "kv-pressure",
                            "discarded": len(run.out)})
        self._queue.appendleft(run.req)
        # its generated-so-far tokens are discarded and will be
        # regenerated on replay: don't count them twice
        self.stats.tokens_out -= len(run.out)
        uid = run.req.uid
        pin = 0
        if self.prefix_cache:
            # keep references on the prefix blocks the replay's split
            # needs — a replay MUST re-admit at the same t_cached with
            # the same physical blocks to regenerate identical tokens
            pin = self._admit_split.get(uid, 0) // self.bs
        kept = run.blocks[:pin]
        before = self._available()
        self._free_slot(victim, keep=pin)
        if kept and self._available() == before:
            # pinning freed nothing (the victim was all prefix):
            # progress beats the pin — drop it, and the uid's split
            # record with it (its replay re-prefills from scratch)
            for b in kept:
                self._release_block(b)
            self._admit_split.pop(uid, None)
            self._force_fresh.add(uid)
        elif kept:
            self._pinned[uid] = kept
        self.stats.preemptions += 1
        return True

    def _ensure_blocks(self, horizons=None) -> None:
        """Every active slot is about to write its next
        ``min(K, remaining)`` positions; make sure the blocks holding
        them exist, preempting if the pool is dry.  In-chunk steps past
        ``remaining`` deliberately get no blocks: their writes fall
        through the zeroed table entries to scratch and their tokens are
        discarded at harvest.  ``horizons`` (speculative mode) overrides
        the per-slot position count: the current token + accepted-prefix
        keys every USED verify query reads must be in real blocks."""
        for slot in list(self._admit_order):
            run = self._running[slot]
            if run is None:
                continue
            if horizons is not None:
                horizon = horizons.get(slot, 1)
            else:
                horizon = min(self.K, run.req.max_new - len(run.out))
            bi = (int(self._pos[slot]) + horizon - 1) // self.bs
            while self._running[slot] is run and bi >= len(run.blocks):
                got = self._alloc(1)
                if got is not None:
                    run.blocks.extend(got)
                    self._tables[slot, len(run.blocks) - 1] = got[0]
                elif not self._preempt_for(slot):
                    raise RuntimeError(
                        "KV pool exhausted with a single active request "
                        "— increase num_blocks")

    # -------------------------------------------------------------- run
    def _step_speculative(self) -> bool:
        """Speculative tick: draft via prompt-lookup, one verify
        dispatch checks every slot's current token + drafts, accept the
        matching prefix + the model's own next token.  Greedy streams
        are EXACTLY the sequential argmax streams (lossless); sampled
        slots draft nothing and behave as 1-token steps with the usual
        key discipline."""
        _t_tick = time.perf_counter()
        self._admit()
        # draft BEFORE ensuring blocks: each slot's block horizon is its
        # accepted-prefix-reachable positions (dlen + 1)
        drafts: Dict[int, List[int]] = {}
        horizons: Dict[int, int] = {}
        for slot in range(self.S):
            run = self._running[slot]
            if run is None:
                continue
            rem = run.req.max_new - len(run.out)
            if run.req.temperature > 0 or rem <= 1:
                drafts[slot] = []
            else:
                drafts[slot] = run.draft(min(self.spec, rem - 1))
            horizons[slot] = len(drafts[slot]) + 1
        self._ensure_blocks(horizons)
        active = [s for s in range(self.S) if self._running[s] is not None]
        if not active:
            return bool(self._queue)
        Q = self.spec + 1
        draft = np.zeros((self.S, Q), np.int32)
        dlen = np.zeros(self.S, np.int32)
        for slot in active:
            d = drafts.get(slot, [])
            draft[slot, 0] = self._tok[slot]
            draft[slot, 1:1 + len(d)] = d
            dlen[slot] = len(d)
        _t_decode = time.perf_counter()
        preds, self.pools = self._verify(
            self.params, self.pools, jnp.asarray(self._tables),
            jnp.asarray(self._pos), jnp.asarray(draft),
            jnp.asarray(self._uid_lo), jnp.asarray(self._uid_hi),
            jnp.asarray(self._tcount), jnp.asarray(self._temp),
            jnp.asarray(self._topk), jnp.asarray(self._topp))
        preds = np.asarray(preds)                    # [S, Q] — ONE sync
        _dt_decode = time.perf_counter() - _t_decode
        # a verify dispatch budgets Q positions per slot (occupancy then
        # reads emitted/(Q*slots), comparable with chunk mode's K)
        self.stats.decode_steps += Q
        self.stats.dispatches += 1
        _tokens_before = self.stats.tokens_out
        for slot in active:
            run = self._running[slot]
            _n0, _uid = len(run.out), run.req.uid
            # longest drafted prefix matching the model's own predictions
            a = 0
            while a < dlen[slot] and draft[slot, a + 1] == preds[slot, a]:
                a += 1
            self.stats.spec_proposed += int(dlen[slot])
            self.stats.spec_accepted += a
            emitted = [int(t) for t in draft[slot, 1:1 + a]] \
                + [int(preds[slot, a])]
            for j, tok in enumerate(emitted):
                run.out.append(tok)
                self.stats.tokens_out += 1
                self.stats.slot_steps += 1
                if self._finished(run):
                    self._harvest(slot)
                    break
            else:
                self._emit(run)
                n_new = len(emitted)
                self._pos[slot] += n_new
                self._tok[slot] = emitted[-1]
                self._tcount[slot] += n_new
            if _trace.armed():
                _trace.event("serving.decode", category="serving",
                             dur=_dt_decode,
                             attrs={"uid": _uid, "slot": slot,
                                    "tokens": len(run.out) - _n0})
        self._observe_decode(_dt_decode,
                             self.stats.tokens_out - _tokens_before)
        self._prof_phases.add("compute", _dt_decode)
        self._prof_phases.publish(time.perf_counter() - _t_tick)
        return True

    def _observe_decode(self, dt: float, emitted: int) -> None:
        """Per-token decode latency: one dispatch's wall time amortized
        over the tokens it emitted (the p50/p99 a traffic bench reads)."""
        if emitted > 0:
            get_monitor().observe("kungfu_tpu_serving_decode_token_seconds",
                                  dt / emitted)

    def step(self) -> bool:
        """One scheduler tick: admit, guarantee memory, ONE device
        program decoding ``K`` tokens for every active slot, harvest.
        Returns False when idle."""
        if self.spec:
            return self._step_speculative()
        _t_tick = time.perf_counter()
        self._admit()
        self._ensure_blocks()
        active = [s for s in range(self.S) if self._running[s] is not None]
        if not active:
            return bool(self._queue)
        _t_decode = time.perf_counter()
        toks, self.pools = self._decode(
            self.params, self.pools, jnp.asarray(self._tables),
            jnp.asarray(self._pos), jnp.asarray(self._tok),
            jnp.asarray(self._uid_lo), jnp.asarray(self._uid_hi),
            jnp.asarray(self._tcount), jnp.asarray(self._temp),
            jnp.asarray(self._topk), jnp.asarray(self._topp))
        toks = np.asarray(toks)                      # [K, S] — ONE sync
        _dt_decode = time.perf_counter() - _t_decode
        self.stats.decode_steps += self.K
        self.stats.dispatches += 1
        _tokens_before = self.stats.tokens_out
        for slot in active:
            run = self._running[slot]
            _n0, _uid = len(run.out), run.req.uid
            for j in range(self.K):
                run.out.append(int(toks[j, slot]))
                self.stats.tokens_out += 1
                self.stats.slot_steps += 1
                if self._finished(run):
                    self._harvest(slot)
                    break
            else:
                self._emit(run)
                self._pos[slot] += self.K
                self._tok[slot] = int(toks[self.K - 1, slot])
                self._tcount[slot] += self.K
            if _trace.armed():
                _trace.event("serving.decode", category="serving",
                             dur=_dt_decode,
                             attrs={"uid": _uid, "slot": slot,
                                    "tokens": len(run.out) - _n0})
        self._observe_decode(_dt_decode,
                             self.stats.tokens_out - _tokens_before)
        self._prof_phases.add("compute", _dt_decode)
        self._prof_phases.publish(time.perf_counter() - _t_tick)
        return True

    @property
    def busy(self) -> bool:
        """Anything queued or decoding."""
        return bool(self._queue) or any(r is not None
                                        for r in self._running)

    def take_results(self) -> Dict[int, List[int]]:
        """Pop and return every finished request so far (uid -> tokens).
        The incremental-harvest API the serving front-end drives between
        step() calls; run() is the batch-mode convenience on top."""
        out, self._results = self._results, {}
        return out

    def run(self, requests) -> Dict[int, List[int]]:
        """Drain ``requests`` through the engine; returns uid -> tokens."""
        t0 = time.perf_counter()
        for r in requests:
            self.submit(r)
        while self.step():
            pass
        self.stats.wall_s += time.perf_counter() - t0
        return self.take_results()
