"""A looped decoder with an exit at every round, in training.

The layer stack of ``models/gpt.py`` run ``GPTConfig.n_rounds`` times under
its one set of weights (a universal transformer in depth): round ``r``
starts from the normed output ``h_{r-1}`` of the round before, and every
``h_r`` is read by the same output head.  An exit gate, one linear map to a
scalar, gives each token a probability of stopping after each round,

    lam_r = sigmoid(w_g . h_r + b_g)
    p_r   = lam_r * prod_{j<r} (1 - lam_j)    for r < R
    p_R   =         prod_{j<R} (1 - lam_j)

and the loss is the exit distribution's expected cross-entropy less ``beta``
times its entropy, the mean over tokens of

    sum_r p_r CE_r  -  beta * H(p),      H(p) = -sum_r p_r log p_r

("Scaling Latent Reasoning via Looped Language Models", stage one).  There
is one ``apply_layer``: the rounds are a ``lax.scan`` over
``gpt.layer_stack``'s one pass, so the program is traced and compiled at
the size of one round.  A weight's gradient is the sum of its rounds',
made by the scan's backward in the weight's own dtype: under
``build_train_step(compute_dtype=bf16)`` a bf16 sum of ``n_rounds`` terms,
which the microbatch accumulation then adds up in f32.

Unsharded only (data-parallel lanes each run the whole of it), and
training only: nothing in ``models/gpt.py`` or ``serving/`` decodes a
looped model (they refuse ``n_rounds != 1``).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.chunked_ce import chunked_cross_entropy
from . import gpt as G
from .gpt import GPTConfig

__all__ = ["init_params", "forward_rounds", "exit_log_probs", "loss_fn"]


def init_params(rng: jax.Array, cfg: GPTConfig) -> Dict:
    """``gpt.init_params`` and the exit gate ``{"w": [D], "b": []}``: the
    weight normal over sqrt(D), so that the gate's logit of a normed state
    is of order one, the bias at nought."""
    k_gate, k_stack = jax.random.split(rng)
    params = G.init_params(k_stack, cfg)
    params["exit_gate"] = {
        "w": (jax.random.normal(k_gate, (cfg.d_model,), jnp.float32)
              / np.sqrt(cfg.d_model)),
        "b": jnp.zeros((), jnp.float32)}
    return params


def forward_rounds(params, tokens, cfg: GPTConfig, *, attn: str = "auto",
                   remat: bool = False):
    """Every round's normed state, ``[n_rounds, B, T, D]``.  ``attn`` and
    ``remat`` as in ``gpt.layer_stack``; ``remat="full"`` keeps, for each
    of the ``n_rounds x n_layers`` layer visits, the layer's input and what
    ``gpt.layer_stack``'s policy names (the flash kernel's output and
    ``lse``; ``wm``'s output under ``cfg.out_norms``), stacked over the
    rounds by the scan, and makes the rest of each visit again."""
    x, run = G.layer_stack(params, tokens, cfg, attn=attn, remat=remat)

    def one_round(x, _):
        x = run(x)
        with jax.named_scope("final_norm"):
            h = G.rms_norm(x, params["lnf"], cfg.norm_eps)
        return h, h

    # around the scan, not inside its body: the loop's own operations (the
    # carries, the stacked residuals, the shared weights' gradients summed)
    # carry the name with the layers'
    with jax.named_scope("ut_loop"):
        _, hs = lax.scan(one_round, x, None, length=cfg.n_rounds)
    return hs


def exit_log_probs(z):
    """``log p`` ``[R, ...]`` of the exit distribution from the gate's
    logits ``z`` ``[R - 1, ...]`` of every round but the last (whose gate
    nothing reads: what has not stopped by then stops there)."""
    one = jnp.zeros((1,) + z.shape[1:], z.dtype)                # log 1
    # log prod_{j<r} (1 - lam_j) for r = 1..R, then log lam_r (the last: 1)
    going = jnp.concatenate([one, jnp.cumsum(jax.nn.log_sigmoid(-z), 0)])
    return going + jnp.concatenate([jax.nn.log_sigmoid(z), one])


def loss_fn(params, tokens, targets, cfg: GPTConfig, *, beta: float,
            ce_chunk: int, attn: str = "auto", remat: bool = False):
    """Mean over tokens of ``sum_r p_r CE_r - beta H(p)``.  Each round's
    per-token cross-entropy comes from ``chunked_cross_entropy`` on the one
    head (no ``[B, T, V]`` logits; ``ce_chunk`` divides the vocabulary).
    With one round there is no gate to read and this is the plain mean
    cross-entropy."""
    hs = forward_rounds(params, tokens, cfg, attn=attn, remat=remat)
    with jax.named_scope("exit_gate"):
        if cfg.n_rounds > 1:
            gate = params["exit_gate"]
            z = jnp.einsum("rbtd,d->rbt", hs[:-1], gate["w"].astype(hs.dtype),
                           preferred_element_type=jnp.float32)
            z = z + gate["b"].astype(jnp.float32)
        else:
            z = jnp.zeros((0,) + targets.shape, jnp.float32)
        logp = exit_log_probs(z)
    head = params["lm_head"].astype(cfg.dtype)
    with jax.named_scope("ce_head"):    # the map's own slices with the heads
        ce = lax.map(lambda h: chunked_cross_entropy(h, head, targets,
                                                     ce_chunk), hs)
    with jax.named_scope("exit_mix"):
        # sum_r p_r CE_r + beta sum_r p_r log p_r
        return jnp.sum(jnp.exp(logp) * (ce + beta * logp), axis=0).mean()
