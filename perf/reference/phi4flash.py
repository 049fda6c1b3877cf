"""Plain reference for the `phi4flash` family (Phi-4-mini-flash-reasoning,
the SambaY decoder-hybrid-decoder of arXiv:2507.06607 on Samba,
arXiv:2406.07522). Published layer l of `L` (`published.num_hidden_layers`)
is, with `mb = mb_per_layer`:

    l < L/2, l % mb == 0     mamba                 (the self-decoder)
    l < L/2, otherwise       attention, windowed to `sliding_window`
    l = L/2                  mamba whose gated scan output is the MEMORY m
    l = L/2 + 1              full attention whose K and V are the SHARED KV
    l > L/2 + 1, even        gated memory unit      (the cross-decoder)
    l > L/2 + 1, odd         cross-attention to the shared KV

Every layer on a sequence x [T, D]: x1 = x + mixer(N1 x), x2 = x1 + Wd
(silu(Wg u) * Wu u) with u = N2 x1 (width intermediate_size); N LayerNorm
with a weight and a bias, eps layer_norm_eps; then the final LayerNorm and
the head tied to the embedding (logits = x wte^T), the mean token
cross-entropy. The mixers, on h = N1 x:

    mamba:  [u, z] = h W_in;  u' = silu(sum_j conv[j] u_{t-j} + b)  (d_conv
            taps, nought before the first position);  [dlt, B, C] = u' W_x;
            delta = softplus(dlt W_dt + b_dt);  A = -exp(A_log)  [E, N];
            s_t = exp(delta_t A) s_{t-1} + (delta_t u'_t) B_t,  s_0 = 0;
            y_t = s_t C_t + D u'_t;  m = y silu(z);  out = m W_out
    attention (differential, arXiv:2410.05258; no positions):
            q = [q1 | q2] (2 x H/2 heads of Dh = D/H), k = [k1 | k2]
            (2 x Hkv/2), v (Hkv/2 heads of 2 Dh); differential head i reads
            KV head i // (H/Hkv);  lam = exp(lq1 . lk1) - exp(lq2 . lk2) +
            lam_init,  lam_init = 0.8 - 0.6 exp(-0.3 l);
            o = (softmax(q1 k1^T / sqrt(Dh)) - lam softmax(q2 k2^T /
            sqrt(Dh))) v, causal (and i - j < window where windowed);
            o = (1 - lam_init) RMSNorm_2Dh(o) (weight `subln`, eps 1e-5);
            out = o Wo
    cross:  the same with q alone projected, k and v the shared KV, full
    gmu:    out = (m silu(h W_g)) W_o

The layers kept are the published layers `first_layer` on; the vocabulary
is the slice the file states.

Straight `jax.numpy` in float32 with every product at `highest`: the scan
as a sequential `lax.scan` over the positions (in checkpointed chunks of
CHUNK positions that carry the state and the convolution's last inputs),
attention a differential head and a block of QROWS queries at a time
against every key, masked; no kernel, no chunked loss and no import of
the program. Checkpoints around each layer, each chunk, each head and
block, each ROWS rows of a feed-forward and each HEAD_ROWS rows of the
head keep a sequence of 16,384 inside the chip beside the optimizer's
state. The scan's elementwise arithmetic is float32 under `quant` too.

Departures from the published model, each also under `assumed` in the
configuration file: the convolution's taps are stored [d_conv, E] with tap
j on position t - j (Hugging Face's `nn.Conv1d` weight is [E, 1, d_conv],
last tap on t); q1, q2 (and k1, k2) are the two halves of the heads; the
weights are drawn here (normal over sqrt(fan-in), the family's starts for
A_log, the step's bias, D and the lam vectors).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import gpt as base
from .gpt import F32

CHUNK = 256         # positions of the scan a checkpoint holds
QROWS = 1024        # queries a block of attention holds
ROWS = 2048         # a feed-forward reads the tokens in blocks of so many
HEAD_ROWS = 2048    # the output head reads the tokens in blocks of so many


def layer_kind(l: int, layers: int, mb: int) -> str:
    """Published layer `l`'s kind: "mamba", "swa", "full", "gmu", "cross"."""
    half = layers // 2
    if l < half:
        return "mamba" if l % mb == 0 else "swa"
    if l == half:
        return "mamba"
    if l == half + 1:
        return "full"
    return "gmu" if l % 2 == 0 else "cross"


def sizes(config: dict) -> dict:
    """The configuration's sizes under the names the equations use."""
    L, first = config["num_hidden_layers"], config["first_layer"]
    published = config["published"]["num_hidden_layers"]
    D, H = config["hidden_size"], config["num_attention_heads"]
    if not config["tie_word_embeddings"] or config["mlp_bias"]:
        raise ValueError("the equations tie the head to the embedding and "
                         "give the feed-forward no bias")
    kinds = tuple(layer_kind(first + i, published, config["mb_per_layer"])
                  for i in range(L))
    keep = range(first, first + L)
    memory, shared = published // 2, published // 2 + 1
    if (("gmu" in kinds and memory not in keep)
            or ("cross" in kinds and shared not in keep)):
        raise ValueError("a kept gmu or cross layer reads a memory or a KV "
                         "from a layer that is not kept")
    return dict(
        D=D, H=H, Hkv=config["num_key_value_heads"], Dh=D // H,
        F=config["intermediate_size"], V=config["vocab_size"], L=L,
        E=config["mamba_expand"] * D, N=config["mamba_d_state"],
        K=config["mamba_d_conv"], R=config["mamba_dt_rank"],
        eps=config["layer_norm_eps"], window=config["sliding_window"],
        kinds=kinds, memory=memory - first, shared=shared - first,
        lam0=tuple(0.8 - 0.6 * math.exp(-0.3 * (first + i))
                   for i in range(L)))


def attention_layers(config: dict) -> int:
    """The kept layers that attend (fit rule 1 holds the step to them)."""
    return sum(k in ("swa", "full", "cross") for k in sizes(config)["kinds"])


def init_params(key, config: dict) -> dict:
    """f32 weights from `key`, in the layout the program reads: normal over
    sqrt(fan-in), norms' weights at one and biases at nought, the
    embedding's rows over sqrt(hidden) (it is the head too); A_log = log
    1..N, the step's bias the inverse softplus of a log-uniform draw in
    [1e-3, 1e-1], D = 1, the lam vectors N(0, 0.1)."""
    s = sizes(config)
    D, H, Hkv, Dh, E, N, K, R, F = (s[k] for k in "D H Hkv Dh E N K R F".split())
    keys = iter(jax.random.split(key, 1 + 16 * s["L"]))

    def dense(shape, fan_in):
        return jax.random.normal(next(keys), shape, F32) / np.sqrt(fan_in)

    def lam():
        return 0.1 * jax.random.normal(next(keys), (Dh,), F32)

    layers = []
    for kind in s["kinds"]:
        layer = {"ln1": jnp.ones((D,), F32), "ln1_b": jnp.zeros((D,), F32),
                 "ln2": jnp.ones((D,), F32), "ln2_b": jnp.zeros((D,), F32),
                 "wi": dense((D, 2, F), D), "wm": dense((F, D), F)}
        if kind == "mamba":
            dt = jnp.exp(jax.random.uniform(next(keys), (E,), F32)
                         * (math.log(0.1) - math.log(1e-3))
                         + math.log(1e-3))
            layer.update(
                w_in=dense((D, 2 * E), D), conv=dense((K, E), K),
                conv_b=jnp.zeros((E,), F32), w_x=dense((E, R + 2 * N), E),
                w_dt=dense((R, E), R), dt_b=dt + jnp.log(-jnp.expm1(-dt)),
                a_log=jnp.log(jnp.broadcast_to(
                    jnp.arange(1, N + 1, dtype=F32), (E, N))),
                d_skip=jnp.ones((E,), F32), w_out=dense((E, D), E))
        elif kind == "gmu":
            layer.update(w_g=dense((D, E), D), w_o=dense((E, D), E))
        else:
            layer.update(wq=dense((D, H, Dh), D),
                         wo=dense((H // 2, 2 * Dh, D), H * Dh))
            if kind != "cross":
                layer.update(wk=dense((D, Hkv, Dh), D),
                             wv=dense((D, Hkv // 2, 2 * Dh), D))
            layer.update(lam_q1=lam(), lam_k1=lam(), lam_q2=lam(),
                         lam_k2=lam(), subln=jnp.ones((2 * Dh,), F32))
        layers.append(layer)
    return {"wte": dense((s["V"], D), D), "layers": layers,
            "lnf": jnp.ones((D,), F32), "lnf_b": jnp.zeros((D,), F32)}


def init_model_state(config: dict) -> dict:
    """No state besides the weights."""
    return {}


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def scan(u, delta, A, B, C, state):
    """The selective scan one position at a time: u, delta [T, E], A
    [E, N], B, C [T, N], state [E, N] -> (state, y [T, E]) without the
    skip."""
    def step(s, t):
        d, x, b, c = t
        s = jnp.exp(d[:, None] * A) * s + (d * x)[:, None] * b[None, :]
        return s, jnp.sum(s * c[None, :], -1)
    return jax.lax.scan(step, state, (delta, u, B, C))


step_size = jax.nn.softplus     # delta = step_size(dlt W_dt + b_dt)


def diff_lambda(layer, lam0):
    """Differential attention's lam of a layer."""
    return (jnp.exp(jnp.sum(layer["lam_q1"] * layer["lam_k1"]))
            - jnp.exp(jnp.sum(layer["lam_q2"] * layer["lam_k2"])) + lam0)


def mamba(mm, s, layer, h):
    """A mamba layer's mixing of one sequence, h [T, D] (normed) ->
    (out [T, D], m [T, E])."""
    T, K, N, R = h.shape[0], s["K"], s["N"], s["R"]
    rows = CHUNK if T % CHUNK == 0 else T

    @jax.checkpoint
    def chunk(carry, h_c):
        state, tail = carry
        u, z = jnp.split(mm("td,de->te", h_c, layer["w_in"]), 2, -1)
        ext = jnp.concatenate([tail, u], 0)             # K - 1 rows before
        c = layer["conv_b"] + sum(
            layer["conv"][j] * ext[K - 1 - j:K - 1 - j + rows]
            for j in range(K))
        u = jax.nn.silu(c)
        dlt, B, C = jnp.split(mm("te,er->tr", u, layer["w_x"]),
                              [R, R + N], -1)
        delta = step_size(mm("tr,re->te", dlt, layer["w_dt"])
                          + layer["dt_b"])
        state, y = scan(u, delta, -jnp.exp(layer["a_log"]), B, C, state)
        m = (y + layer["d_skip"] * u) * jax.nn.silu(z)
        return (state, ext[rows:]), (mm("te,ed->td", m, layer["w_out"]), m)

    E = s["E"]
    start = (jnp.zeros((E, N), F32), jnp.zeros((K - 1, E), F32))
    _, (out, m) = jax.lax.scan(chunk, start,
                               h.reshape(T // rows, rows, -1))
    return out.reshape(T, -1), m.reshape(T, -1)


def _by_rows(fn, *arrays):
    """``fn(*blocks)`` over blocks of ROWS rows of the arrays, each made
    again in the backward pass."""
    n = arrays[0].shape[0]
    rows = ROWS if n % ROWS == 0 else n
    blocks = lambda t: t.reshape((n // rows, rows) + t.shape[1:])
    out = jax.lax.map(lambda a: jax.checkpoint(fn)(*a),
                      tuple(blocks(t) for t in arrays))
    return out.reshape((n,) + out.shape[2:])


def gmu(mm, layer, h, m):
    return _by_rows(lambda h, m: mm("te,ed->td", m * jax.nn.silu(
        mm("td,de->te", h, layer["w_g"])), layer["w_o"]), h, m)


def _diff_head(mm, window, lam, q1, q2, k1, k2, v):
    """One differential head on one sequence: q1, q2, k1, k2 [T, Dh], v
    [T, 2 Dh]; a block of QROWS queries at a time."""
    T = q1.shape[0]
    rows = QROWS if T % QROWS == 0 else T

    @jax.checkpoint
    def block(a):
        i, q1, q2 = a
        ahead = (i * rows + jnp.arange(rows))[:, None] - jnp.arange(T)[None]
        seen = ahead >= 0 if window is None else (ahead >= 0) & (
            ahead < window)
        soft = lambda q, k: jax.nn.softmax(jnp.where(
            seen, mm("qd,kd->qk", q, k) / np.sqrt(q.shape[-1]), -jnp.inf),
            -1)
        return mm("qk,kd->qd", soft(q1, k1) - lam * soft(q2, k2), v)
    n = T // rows
    return jax.lax.map(block, (jnp.arange(n), q1.reshape(n, rows, -1),
                               q2.reshape(n, rows, -1))).reshape(T, -1)


def attention(mm, s, index, layer, h, kv=None):
    """A (differential) attention layer's mixing of one sequence, h [T, D]
    -> (out, (k, v)); `kv` given, cross-attention to it."""
    kind = s["kinds"][index]
    proj = lambda w: mm("td,dhk->thk", h, layer[w])
    q = proj("wq")
    k, v = (proj("wk"), proj("wv")) if kv is None else kv
    lam0 = s["lam0"][index]
    lam = diff_lambda(layer, lam0)
    heads, kvh = s["H"] // 2, s["Hkv"] // 2
    g = heads // kvh
    by_head = lambda t: t.transpose(1, 0, 2)
    rep = lambda t: jnp.repeat(by_head(t), g, axis=0)
    one = functools.partial(_diff_head, mm,
                            s["window"] if kind == "swa" else None, lam)
    o = jax.lax.map(lambda a: jax.checkpoint(one)(*a), (
        by_head(q[:, :heads]), by_head(q[:, heads:]), rep(k[:, :kvh]),
        rep(k[:, kvh:]), rep(v)))                         # [heads, T, 2 Dh]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5)
    o = o * layer["subln"] * (1 - lam0)
    return mm("htk,hkd->td", o, layer["wo"]), (k, v)


def _layer(mm, s, index, layer, x, carry):
    """Block `index` on one sequence, x [T, D] -> (x, carry): the carry
    holds the memory and the shared KV once made."""
    kind = s["kinds"][index]
    h = layer_norm(x, layer["ln1"], layer["ln1_b"], s["eps"])
    carry = dict(carry)
    if kind == "mamba":
        out, m = mamba(mm, s, layer, h)
        if index == s["memory"]:
            carry["memory"] = m
    elif kind == "gmu":
        out = gmu(mm, layer, h, carry["memory"])
    else:
        out, kv = attention(mm, s, index, layer, h,
                            carry["kv"] if kind == "cross" else None)
        if index == s["shared"]:
            carry["kv"] = kv
    x = x + out
    u = layer_norm(x, layer["ln2"], layer["ln2_b"], s["eps"])
    wi = layer["wi"].reshape(s["D"], 2 * s["F"])

    def ffn(u):
        a = mm("td,df->tf", u, wi)
        return mm("tf,fd->td", jax.nn.silu(a[:, :s["F"]]) * a[:, s["F"]:],
                  layer["wm"])
    return x + _by_rows(ffn, u), carry


def sequence_loss(params, tokens, targets, config: dict, quant="none"):
    """Summed token cross-entropy of one sequence, tokens [T]."""
    s, mm = sizes(config), base._mm(quant)
    x, carry = params["wte"][tokens], {}
    for i, layer in enumerate(params["layers"]):
        x, carry = jax.checkpoint(functools.partial(_layer, mm, s, i))(
            layer, x, carry)
    x = layer_norm(x, params["lnf"], params["lnf_b"], s["eps"])

    @jax.checkpoint
    def block_ce(x, w, targets):
        logits = mm("td,vd->tv", x, w)
        picked = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)

    n = x.shape[0]
    rows = HEAD_ROWS if n % HEAD_ROWS == 0 else n
    return jnp.sum(jax.lax.map(
        lambda a: block_ce(a[0], params["wte"], a[1]),
        (x.reshape(n // rows, rows, -1), targets.reshape(n // rows, rows))))


def batch_loss(params, batch, config: dict, quant="none"):
    """Mean token cross-entropy of a batch (tokens, targets [B, T]); the
    sequences one after another, into ONE tree of gradients."""
    tokens, targets = batch
    total = sum(sequence_loss(params, tokens[i], targets[i], config, quant)
                for i in range(tokens.shape[0]))
    return total / tokens.size


def loss_and_grads(params, mstate, batch, config: dict, quant="none"):
    value, grads = jax.value_and_grad(batch_loss)(params, batch, config,
                                                  quant)
    return value, grads, mstate
