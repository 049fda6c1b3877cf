"""Plain reference for the `smallthinker` family: a pre-norm decoder whose
feed-forward is routed over sparse experts without dropped tokens, whose
router reads what attention reads, and whose layers are of two kinds.
Layer l on a sequence x [T, D], every norm RMS, no biases:

    h  = N1(x)
    q, k, v = h Wq, h Wk, h Wv      (H query heads, Hkv KV heads of Dh;
                                     H Dh is not D)
    where rope_layout[l] = 1, q and k are rotated (half-split over the
    head); where it is 0 they are not, and carry no position (NoPE)
    scores q k^T / sqrt(Dh), causal, and where sliding_window_layout[l] = 1
    only i - j < window; query head a reads KV head a // (H / Hkv)
    x1 = x + concat(softmax(scores) v) Wo
    p  = softmax(h Wr) over ALL E experts, in float32: the router reads the
         attention's input, not the feed-forward's
    S  = the k largest of p (of equals the lower id);  w_e = p_e / sum_S p
    u  = N2(x1)
    x2 = x1 + sum over e in S that are HELD HERE of
              w_e Wd_e (relu(Wg_e u) * (Wu_e u))                  (ReGLU)

then the final norm, an untied head and the mean token cross-entropy; no
auxiliary loss. This chip holds `moe_num_primary_experts` experts from id
`first_held_expert` on, of the `published` count the router keeps; what the
experts held elsewhere would add is left out, as in the program. The
vocabulary is the slice the file states.

Straight `jax.numpy` in float32 with every product at `highest`: dense
masked attention a query head at a time, a loop over the held experts in
which every expert sees every token and a mask of weights picks its own,
the k largest by k passes of argmax; no sort, no kernel, no chunked loss
and no import of the program. A checkpoint around each layer, each head of
attention, each expert and each 2048 tokens under the output head keeps
two sequences of 8192 inside the chip beside the optimizer's state. The
router's product is float32 under `quant` too (the configuration states it
so).

**The weights come from the configuration's `weights_key`, not from the key
handed in** (every caller hands in the run's seed): which experts are
popular is decided by the weights, so weights drawn from the seed would give
every seed another amount of work in the grouped products. The token pool
is the seed's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import gpt as base
from .gpt import F32


def sizes(config: dict) -> dict:
    """The configuration's sizes under the names the equations use."""
    L = config["num_hidden_layers"]
    if not config["moe_primary_router_apply_softmax"]:
        raise ValueError("the router is a softmax over all experts")
    return dict(
        D=config["hidden_size"], H=config["num_attention_heads"],
        Hkv=config["num_key_value_heads"], Dh=config["head_dim"],
        F=config["moe_ffn_hidden_size"], V=config["vocab_size"], L=L,
        eps=config["rms_norm_eps"], theta=config["rope_theta"],
        E=config["published"]["moe_num_primary_experts"],
        k=config["moe_num_active_primary_experts"],
        first=config["first_held_expert"],
        G=config["moe_num_primary_experts"],
        norm_topk=config["norm_topk_prob"],
        window=config["sliding_window_size"],
        rope=tuple(config["rope_layout"][:L]),
        windowed=tuple(config["sliding_window_layout"][:L]))


def init_params(key, config: dict) -> dict:
    """f32 weights, normal over sqrt(fan-in) (the embedding's fan-in is
    one), norms at one, in the layout the program reads; from
    `weights_key`, whatever `key` is."""
    del key
    s = sizes(config)
    D, H, Hkv, Dh, F, V = s["D"], s["H"], s["Hkv"], s["Dh"], s["F"], s["V"]
    keys = iter(jax.random.split(jax.random.PRNGKey(config["weights_key"]),
                                 2 + 7 * s["L"]))

    def dense(shape, fan_in):
        return jax.random.normal(next(keys), shape, F32) / np.sqrt(fan_in)

    layers = [{
        "ln1": jnp.ones((D,), F32),
        "wq": dense((D, H, Dh), D), "wk": dense((D, Hkv, Dh), D),
        "wv": dense((D, Hkv, Dh), D), "wo": dense((H, Dh, D), H * Dh),
        "ln2": jnp.ones((D,), F32),
        "router": dense((D, s["E"]), D),
        "wi": dense((s["G"], D, 2 * F), D), "wm": dense((s["G"], F, D), F),
    } for _ in range(s["L"])]
    # the embedding's rows are unit normal: a lookup's fan-in is one. At
    # 1 / sqrt(D) a token's own row (rms 0.02) is smaller than what
    # attention adds to it, a mean over its context that every token of a
    # sequence shares, so from the second layer on the router sees the
    # sequence and not the token and sends a whole sequence to the same six
    # experts: the held experts' rows then swing by a factor of three with
    # the seed's tokens and drift by half in 70 steps (PERF.md section 6,
    # PR 35). No trained model routes so.
    return {"wte": dense((V, D), 1), "layers": layers,
            "lnf": jnp.ones((D,), F32), "lm_head": dense((D, V), D)}


def _attend_head(mm, window, q, k, v):
    """One query head against its KV head: q, k, v [T, Dh]; `window` None
    for a full layer."""
    T = q.shape[0]
    ahead = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    seen = ahead >= 0 if window is None else (ahead >= 0) & (ahead < window)
    s = jnp.where(seen, mm("qd,kd->qk", q, k) / np.sqrt(q.shape[-1]),
                  -jnp.inf)
    return mm("qk,kd->qd", jax.nn.softmax(s, -1), v)


def routing_weights(p, k: int, normalize: bool):
    """[T, E] weights from the router's probabilities: p_e on each token's
    k largest (of equals the lower id), nought elsewhere; with `normalize`
    over their sum."""
    rest, picked = p, jnp.zeros(p.shape, bool)
    for _ in range(k):
        best = jnp.argmax(rest, -1)[:, None] == jnp.arange(p.shape[-1])
        picked, rest = picked | best, jnp.where(best, -1.0, rest)
    w = jnp.where(picked, p, 0.0)
    return w / jnp.sum(w, -1, keepdims=True) if normalize else w


def _expert(mm, u, wi, wm, w):
    """One expert on every token, weighted: u [T, D], w [T]."""
    g, F = mm("td,df->tf", u, wi), wm.shape[0]
    return w[:, None] * mm("tf,fd->td", jax.nn.relu(g[:, :F]) * g[:, F:], wm)


def held_experts(mm, s, layer, u, w):
    """The held experts' part of the routed feed-forward: u [T, D], w
    [T, E] from `routing_weights`."""
    mine = w[:, s["first"]:s["first"] + s["G"]].T               # [G, T]
    one = jax.checkpoint(functools.partial(_expert, mm, u))

    def add(y, e):
        return y + one(*e), None
    return jax.lax.scan(add, jnp.zeros_like(u),
                        (layer["wi"], layer["wm"], mine))[0]


def _layer(mm, s, index, layer, x):
    """Block `index` on a batch of sequences, x [B, T, D]."""
    B, T, D = x.shape
    h = base._rms(x, layer["ln1"], s["eps"])
    q = mm("btd,dhk->bthk", h, layer["wq"])
    k = mm("btd,dhk->bthk", h, layer["wk"])
    v = mm("btd,dhk->bthk", h, layer["wv"])
    if s["rope"][index]:
        rope = jax.vmap(lambda t: base._rope(t, s["theta"]))
        q, k = rope(q), rope(k)
    g = s["H"] // s["Hkv"]
    # [B, T, heads, Dh] -> one row of [T, Dh] a (sequence, query head)
    rows = lambda t: t.transpose(0, 2, 1, 3).reshape(B * s["H"], T, s["Dh"])
    kv = lambda t: rows(jnp.repeat(t, g, axis=2))
    # a query head at a time, made again in the backward pass: the [T, T]
    # scores of 28 heads at 8192 would not fit beside the optimizer state
    attend = jax.checkpoint(functools.partial(
        _attend_head, mm, s["window"] if s["windowed"][index] else None))
    o = jax.lax.map(lambda a: attend(*a), (rows(q), kv(k), kv(v)))
    o = o.reshape(B, s["H"], T, s["Dh"]).transpose(0, 2, 1, 3)
    x = x + mm("bthk,hkd->btd", o, layer["wo"])
    # the router reads the attention's input, in float32 whatever `quant`
    p = jax.nn.softmax(jnp.einsum("td,de->te", h.reshape(B * T, D),
                                  layer["router"], precision="highest"), -1)
    w = routing_weights(p, s["k"], s["norm_topk"])
    u = base._rms(x, layer["ln2"], s["eps"])
    return x + held_experts(mm, s, layer, u.reshape(B * T, D),
                            w).reshape(B, T, D)


HEAD_ROWS = 2048    # the output head reads the tokens in blocks of so many


def loss(params, batch, config: dict, quant="none"):
    """Mean token cross-entropy of a batch, (tokens [B, T], targets [B, T]).
    The sequences go through each layer together (attention a sequence and
    a head at a time), so the backward makes ONE tree of gradients: two
    (`perf/reference/gpt.py` sums the sequences' in a scan) do not fit the
    chip beside the optimizer's state at 656 M weights."""
    tokens, targets = batch
    s, mm = sizes(config), base._mm(quant)
    x = params["wte"][tokens]
    for i, layer in enumerate(params["layers"]):
        x = jax.checkpoint(functools.partial(_layer, mm, s, i))(layer, x)
    x = base._rms(x, params["lnf"], s["eps"]).reshape(-1, x.shape[-1])

    @jax.checkpoint
    def block_ce(x, w, targets):
        logits = mm("td,dv->tv", x, w)
        picked = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)

    n = x.shape[0]
    rows = HEAD_ROWS if n % HEAD_ROWS == 0 else n
    sums = jax.lax.map(
        lambda a: block_ce(a[0], params["lm_head"], a[1]),
        (x.reshape(n // rows, rows, -1), targets.reshape(n // rows, rows)))
    return jnp.sum(sums) / n


def init_model_state(config: dict) -> dict:
    """No state besides the weights."""
    return {}


def loss_and_grads(params, mstate, batch, config: dict, quant="none"):
    value, grads = jax.value_and_grad(loss)(params, batch, config, quant)
    return value, grads, mstate
