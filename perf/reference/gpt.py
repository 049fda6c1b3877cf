"""Plain reference for the `gpt` family: a pre-norm decoder with RMSNorm,
rotary positions (half-split, as Hugging Face's `rotate_half`), grouped-query
causal attention, a SwiGLU feed-forward and an untied output head, trained
with the mean token cross-entropy under AdamW.

Straight `jax.numpy` in float32 with every product at `highest` precision:
no kernel, no chunked loss, no cast copy of the weights. It imports nothing
of the program. The weights it trains are made here from the seed, in the
layout the program's `models/gpt.py` reads (`wq` [D, H, Dh], `wi`
[D, 2, F] ...), and the same arrays are handed to the program.

`quant` puts every matrix product's operands through a lower precision
first. That is the control of `correct`: this reference computed one step
below what the configuration states (fp8 for a bf16 configuration).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUANT = {"none": None, "bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}


def sizes(config: dict) -> dict:
    """The configuration's sizes under the names the equations use."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    return dict(D=d, H=h, Hkv=config["num_key_value_heads"],
                Dh=config.get("head_dim", d // h),
                F=config["intermediate_size"], V=config["vocab_size"],
                L=config["num_hidden_layers"],
                eps=config["rms_norm_eps"], theta=config["rope_theta"])


def init_params(key, config: dict) -> dict:
    """f32 weights from `key`: normal over sqrt(fan-in), norms at one."""
    s = sizes(config)
    D, H, Hkv, Dh, F, V = s["D"], s["H"], s["Hkv"], s["Dh"], s["F"], s["V"]
    keys = iter(jax.random.split(key, 2 + 6 * s["L"]))

    def dense(shape, fan_in):
        return jax.random.normal(next(keys), shape, F32) / np.sqrt(fan_in)

    layers = [{
        "ln1": jnp.ones((D,), F32),
        "wq": dense((D, H, Dh), D), "wk": dense((D, Hkv, Dh), D),
        "wv": dense((D, Hkv, Dh), D), "wo": dense((H, Dh, D), D),
        "ln2": jnp.ones((D,), F32),
        "wi": dense((D, 2, F), D), "wm": dense((F, D), F),
    } for _ in range(s["L"])]
    return {"wte": dense((V, D), D), "layers": layers,
            "lnf": jnp.ones((D,), F32), "lm_head": dense((D, V), D)}


def _mm(quant):
    """`einsum` at float32 `highest`, operands rounded through `quant`."""
    qd = QUANT[quant]

    def mm(eq, a, b):
        if qd is not None:
            a, b = a.astype(qd).astype(F32), b.astype(qd).astype(F32)
        return jnp.einsum(eq, a, b, precision="highest",
                          preferred_element_type=F32)
    return mm


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(t, theta):
    """[T, heads, Dh], position = row."""
    half = t.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(t.shape[0], dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos], -1)


def _attend_group(mm, q, k, v):
    """One KV head with its query heads: q [T, g, Dh], k, v [T, Dh]."""
    T = q.shape[0]
    s = mm("qgd,kd->gqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.arange(T)[:, None] >= jnp.arange(T)[None, :], s,
                  -jnp.inf)
    return mm("gqk,kd->qgd", jax.nn.softmax(s, -1), v)


def _layer(mm, s, layer, x):
    """One block on one sequence, x [T, D]."""
    T = x.shape[0]
    g = s["H"] // s["Hkv"]
    h = _rms(x, layer["ln1"], s["eps"])
    q = _rope(mm("td,dhk->thk", h, layer["wq"]), s["theta"])
    k = _rope(mm("td,dhk->thk", h, layer["wk"]), s["theta"])
    v = mm("td,dhk->thk", h, layer["wv"])
    qg = q.reshape(T, s["Hkv"], g, s["Dh"]).transpose(1, 0, 2, 3)
    # one KV head at a time, recomputed in the backward pass: the [T, T]
    # scores of all heads at once would not fit beside the optimizer state
    o = jax.lax.map(
        lambda a: jax.checkpoint(functools.partial(_attend_group, mm))(*a),
        (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(1, 0, 2, 3).reshape(T, s["H"], s["Dh"])
    x = x + mm("thk,hkd->td", o, layer["wo"])
    h = _rms(x, layer["ln2"], s["eps"])
    u = mm("td,dcf->tcf", h, layer["wi"])
    return x + mm("tf,fd->td", jax.nn.silu(u[:, 0]) * u[:, 1], layer["wm"])


def sequence_loss(params, tokens, targets, config: dict, quant="none"):
    """Mean token cross-entropy of one sequence, tokens [T]."""
    s, mm = sizes(config), _mm(quant)
    x = params["wte"][tokens]
    for layer in params["layers"]:
        x = jax.checkpoint(functools.partial(_layer, mm, s))(layer, x)
    x = _rms(x, params["lnf"], s["eps"])

    def head(x, w, targets):
        logits = mm("td,dv->tv", x, w)
        picked = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)
    return jax.checkpoint(head)(x, params["lm_head"], targets)


def batch_loss(params, batch, config: dict, quant="none"):
    """Mean over the batch's sequences (all of one length, so also the mean
    over its tokens). batch = (tokens [B, T], targets [B, T])."""
    tokens, targets = batch
    per_seq = [sequence_loss(params, tokens[i], targets[i], config, quant)
               for i in range(tokens.shape[0])]
    return sum(per_seq) / len(per_seq)


def init_model_state(config: dict) -> dict:
    """No state besides the weights."""
    return {}


def loss_and_grads(params, mstate, batch, config: dict, quant="none"):
    """Loss and gradient of the batch mean, one sequence at a time so that
    only one sequence's activations are alive."""
    tokens, targets = batch
    n = tokens.shape[0]
    vg = jax.value_and_grad(sequence_loss)

    def body(carry, row):
        loss_acc, grad_acc = carry
        loss, grads = vg(params, row[0], row[1], config, quant)
        return (loss_acc + loss,
                jax.tree_util.tree_map(jnp.add, grad_acc, grads)), None

    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    (loss, grads), _ = jax.lax.scan(body, (jnp.zeros((), F32), zeros),
                                    (tokens, targets))
    return loss / n, jax.tree_util.tree_map(lambda g: g / n, grads), mstate
