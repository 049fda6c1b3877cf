"""Plain reference for the `ouro` family: a looped decoder with an exit at
every round ("Scaling Latent Reasoning via Looped Language Models").

    x = E[tokens]
    for r = 1..R, for l = 1..L, the same weights in every round:
        a = x + N2_l(Attn_l(N1_l(x)));  x = a + N4_l(SwiGLU_l(N3_l(a)))
    after a round's last layer  h_r = N_f(x),  and the next round starts
    from h_r
    lam_r = sigmoid(w_g . h_r + b_g)
    p_r = lam_r prod_{j<r}(1 - lam_j) for r < R,  p_R = prod_{j<R}(1 - lam_j)
    CE_r = logsumexp(h_r W) - (h_r W)[target]
    loss = mean over tokens of  sum_r p_r CE_r - beta H(p),
           H(p) = -sum_r p_r log p_r

Four RMS norms a layer, one before and one after each sub-block; causal
multi-head attention with rotary positions over the whole head
(half-split); no biases but the gate's; one head for all rounds. The
sub-blocks' arithmetic (the norm, the rotation, one KV head's attention,
the rounding of a product's operands under `quant`) is
`perf/reference/gpt`'s,
the family this one grows out of; the layer, the rounds, the gate and the
loss are written out here.

Straight `jax.numpy` in float32 with every product at `highest` precision:
no kernel, no chunked loss, no import of the program; one sequence at a
time through each layer visit and each round's head, with a checkpoint
around each and around each round, so that the published widths fit one
chip beside the optimizer's state (13.4 GiB of 15.75 as the chip's
compiler lays it out, the fp8 control as much; without the checkpoint
around a round the control is refused at 16.46). The weights are made
here from the seed in the layout the program reads, and the same arrays
are handed to the program.
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
    return dict(base.sizes(config), R=config["total_ut_steps"],
                beta=config["exit_entropy_beta"])


def init_params(key, config: dict) -> dict:
    """f32 weights from `key`: normal over sqrt(fan-in), the gate's weight
    too (so its logit of a normed state is of order one), norms at one, the
    gate's bias at nought."""
    D = config["hidden_size"]
    k_gate, k_stack = jax.random.split(key)
    params = base.init_params(k_stack, config)
    for layer in params["layers"]:
        layer["ln1_out"] = jnp.ones((D,), F32)
        layer["ln2_out"] = jnp.ones((D,), F32)
    params["exit_gate"] = {
        "w": jax.random.normal(k_gate, (D,), F32) / np.sqrt(D),
        "b": jnp.zeros((), F32)}
    return params


def _layer(mm, s, layer, x):
    """One block on one sequence, x [T, D]: each sub-block normed going in
    and coming out."""
    T = x.shape[0]
    h = base._rms(x, layer["ln1"], s["eps"])
    q = base._rope(mm("td,dhk->thk", h, layer["wq"]), s["theta"])
    k = base._rope(mm("td,dhk->thk", h, layer["wk"]), s["theta"])
    v = mm("td,dhk->thk", h, layer["wv"])
    # one head at a time (16 KV heads for 16 query heads: groups of one),
    # recomputed in the backward pass: the [T, T] scores of all heads at
    # once would not fit beside the optimizer state
    g = s["H"] // s["Hkv"]
    qg = q.reshape(T, s["Hkv"], g, s["Dh"]).transpose(1, 0, 2, 3)
    o = jax.lax.map(
        lambda a: jax.checkpoint(
            functools.partial(base._attend_group, mm))(*a),
        (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(1, 0, 2, 3).reshape(T, s["H"], s["Dh"])
    a = x + base._rms(mm("thk,hkd->td", o, layer["wo"]), layer["ln1_out"],
                      s["eps"])
    h = base._rms(a, layer["ln2"], s["eps"])
    u = mm("td,dcf->tcf", h, layer["wi"])
    m = mm("tf,fd->td", jax.nn.silu(u[:, 0]) * u[:, 1], layer["wm"])
    return a + base._rms(m, layer["ln2_out"], s["eps"])


def exit_distribution(lam):
    """p [R, ...] from the gates lam [R, ...] (the last round's is not
    read)."""
    R = lam.shape[0]
    p, going = [], jnp.ones_like(lam[0])
    for r in range(R - 1):
        p.append(lam[r] * going)
        going = going * (1.0 - lam[r])
    return jnp.stack(p + [going])


def _token_ce(mm, x, w, targets):
    """Each token's cross-entropy, [T], from all of its logits."""
    logits = mm("td,dv->tv", x, w)
    picked = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return jax.nn.logsumexp(logits, -1) - picked


def sequence_loss(params, tokens, targets, config: dict, quant="none"):
    """The loss of one sequence, tokens [T]."""
    s, mm = sizes(config), base._mm(quant)
    visit = jax.checkpoint(functools.partial(_layer, mm, s))
    head = jax.checkpoint(functools.partial(_token_ce, mm))
    gate = params["exit_gate"]

    # a round is checkpointed whole as well: the backward keeps one state a
    # round and makes the round's layer inputs again when it gets there
    @jax.checkpoint
    def one_round(x, _):
        for layer in params["layers"]:
            x = visit(layer, x)
        h = base._rms(x, params["lnf"], s["eps"])
        lam = jax.nn.sigmoid(mm("td,d->t", h, gate["w"]) + gate["b"])
        return h, (lam, head(h, params["lm_head"], targets))

    _, (lam, ce) = jax.lax.scan(one_round, params["wte"][tokens], None,
                                length=s["R"])                   # [R, T]
    p = exit_distribution(lam)
    entropy = -jnp.sum(p * jnp.log(p), 0)
    return jnp.mean(jnp.sum(p * ce, 0) - s["beta"] * entropy)


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
