"""GPT-style causal transformer LM, designed for composable 3D parallelism.

The reference framework is data-parallel only (SURVEY.md §2.4); this model
family is the TPU-native extension that composes every parallel axis this
framework provides in one train step:

- **dp** — batch data parallelism (the reference's envelope),
- **sp** — sequence/context parallelism: ring attention (`lax.ppermute`
  KV rotation) or Ulysses (`all_to_all` head re-sharding),
- **tp** — Megatron-style tensor parallelism: attention heads and MLP
  features column/row-sharded, vocab-sharded LM head with a parallel
  softmax cross-entropy (max/psum over the tp axis).

TPU-first choices: bias-free blocks (all FLOPs are large matmuls for the
MXU; it also makes the gradient-sync rule uniform — every parameter's
local gradient is a *partial* sum, so replicated params psum over
(dp, sp, tp) and tp-sharded params over (dp, sp)); bf16 activations with
f32 layernorms/softmax; static shapes and unrolled layer loop for XLA.

Functions here are pure and run either unsharded (oracle) or inside
``shard_map`` with the axis names passed in (see
kungfu_tpu/parallel/threed.py for the mesh/step builder).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..parallel.ring_attention import (reference_attention, ring_attention,
                                       ulysses_attention)


# a conv layer's taps a channel: LFM2's ``conv_L_cache``
CONV_TAPS = 3


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 32768
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 6
    d_ff: int = 2048
    max_seq: int = 1024
    dtype: Any = jnp.bfloat16
    # grouped-query attention: number of KV heads (None = n_heads, i.e.
    # MHA).  Shrinks KV projections and, above all, the decode KV cache
    # by n_heads/n_kv_heads
    n_kv_heads: Optional[int] = None
    # rotary position embeddings instead of the learned wpe table (no
    # max_seq-bound position parameters; the LLaMA-style configuration
    # together with bias-free blocks + GQA).  One value for every layer,
    # or a tuple as long as the layers: a layer whose entry is False
    # rotates nothing and has no other position signal (NoPE; such a model
    # has no wpe table either)
    rope: Any = False
    # dtype for the RoPE cos/sin rotation math.  None = activation dtype
    # (fast: no extra HBM pass).  With a bf16 activation dtype the 8-bit
    # mantissa makes the rotation error grow with absolute position —
    # fine at seq 2k-8k, a silent quality risk far past that; set
    # rope_dtype=jnp.float32 for long-context runs to opt back into
    # full-precision rotation (costs one f32 round-trip on [B,T,H,Dh])
    rope_dtype: Any = None
    # FFN nonlinearity: "gelu" (GPT-2 style) or "swiglu" (LLaMA style;
    # wi holds gate and up projections as [D, 2, d_ff] — gate/up packed
    # into ONE [D, 2*d_ff] matmul at apply time (a free reshape; d_ff
    # stays the minor axis for clean MXU tiling — measured ~35% faster
    # than a [D, d_ff, 2] layout whose minor dim is 2 on v5e) and tensor
    # parallelism shards d_ff with gate/up pairs kept together) or
    # "reglu" (the same layout, relu(gate) * up)
    mlp: str = "gelu"
    # what a published configuration states beside its widths: the eps
    # inside every RMS norm and the base of the rotary frequencies
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    # a second RMS norm on each sub-block's OUTPUT, before the residual
    # add (leaves ``ln1_out``, ``ln2_out``): x + N(Attn(N(x))), then
    # a + N(FFN(N(a)))
    out_norms: bool = False
    # how many times the whole stack runs under its one set of weights,
    # each round starting from the last one's normed output
    # (models/looped.py trains it; nothing here decodes it)
    n_rounds: int = 1
    # the size of a head where a configuration states it apart from the
    # hidden size (28 heads of 128 over a hidden size of 2560); None: the
    # quotient d_model // n_heads, and only then must it divide
    d_head: Optional[int] = None
    # sliding-window attention: a query sees the ``window`` newest
    # positions, itself included (i - j < window).  None: plain causal;
    # one value for every layer, or a tuple as long as the layers whose
    # None entries are the full layers
    window: Any = None
    # a routed feed-forward without dropped tokens (parallel/moe.py
    # dropless_moe_ffn): the router's width (experts routed over; 0: the
    # dense FFN above), experts a token, an expert's width, and which
    # experts this chip holds, (first id, count), None: all.  Experts are
    # gated like the dense FFN (``mlp`` "reglu" or "swiglu")
    n_experts: int = 0
    experts_per_token: int = 0
    d_expert: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    # which layers route: None, every layer; or a tuple of bools as long as
    # the layers, the others keeping the dense FFN of ``d_ff``
    routed: Optional[Tuple[bool, ...]] = None
    # how the router scores (parallel/moe.py route_topk): "softmax" over
    # all experts, or "sigmoid", chosen with a bias on the choice that the
    # train step moves and that never weighs (``layer_stack``'s
    # ``router_bias``); and what it reads: "ln1", the block's normed input
    # (what attention reads), or "ln2", what the experts read
    router: str = "softmax"
    router_reads: str = "ln1"
    # what mixes a layer's tokens: "attn", or "conv", a gated short
    # convolution over ``CONV_TAPS`` positions (:func:`_short_conv`);
    # one value for every layer, or a tuple as long as the layers
    operator: Any = "attn"
    # an RMS norm over the head dimension on q and on k, before RoPE
    # (leaves ``q_norm``, ``k_norm``)
    qk_norm: bool = False

    def __post_init__(self):
        if self.d_head is None and self.d_model % self.n_heads != 0:
            raise ValueError(f"d_model {self.d_model} not divisible by "
                             f"n_heads {self.n_heads}")
        for name in ("rope", "window", "operator", "routed"):
            value = getattr(self, name)
            if isinstance(value, (tuple, list)) and (
                    len(value) != self.n_layers):
                raise ValueError(f"{name} has {len(value)} entries for "
                                 f"{self.n_layers} layers")
        ops = {self.layer_operator(i) for i in range(self.n_layers)}
        if not ops <= {"attn", "conv"}:
            raise ValueError(f"operator must be 'attn' or 'conv', got "
                             f"{sorted(ops)}")
        if self.n_experts:
            first, count = self.held
            if not (0 < self.experts_per_token <= self.n_experts
                    and self.d_expert > 0
                    and self.mlp in ("reglu", "swiglu")
                    and 0 <= first and count > 0
                    and first + count <= self.n_experts):
                raise ValueError(
                    "a routed feed-forward needs experts_per_token in "
                    "1..n_experts, d_expert, a gated mlp ('reglu' or "
                    "'swiglu') and experts_held inside the "
                    f"{self.n_experts} routed over")
            if (self.router not in ("softmax", "sigmoid")
                    or self.router_reads not in ("ln1", "ln2")):
                raise ValueError(f"router {self.router!r} reading "
                                 f"{self.router_reads!r}: softmax or "
                                 f"sigmoid, reading ln1 or ln2")
            if "conv" in ops and self.router_reads != "ln2":
                raise ValueError("a conv layer's router reads ln2: it has "
                                 "no attention input to read")
        elif self.routed is not None:
            raise ValueError("routed layers need n_experts")
        if self.n_kv_heads is not None and self.n_kv_heads <= 0:
            raise ValueError(f"n_kv_heads must be positive, "
                             f"got {self.n_kv_heads}")
        if self.n_heads % self.kv_heads != 0:
            raise ValueError(f"n_heads {self.n_heads} not divisible by "
                             f"n_kv_heads {self.kv_heads}")
        if self.rope and self.head_dim % 2 != 0:
            raise ValueError(f"RoPE needs an even head_dim, "
                             f"got {self.head_dim}")
        if self.mlp not in ("gelu", "swiglu", "reglu"):
            raise ValueError(f"mlp must be 'gelu', 'swiglu' or 'reglu', "
                             f"got {self.mlp!r}")
        if self.n_rounds < 1:
            raise ValueError(f"n_rounds must be >= 1, got {self.n_rounds}")

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    def layer_rope(self, i: int) -> bool:
        return bool(self.rope[i] if isinstance(self.rope, (tuple, list))
                    else self.rope)

    def layer_window(self, i: int) -> Optional[int]:
        return (self.window[i] if isinstance(self.window, (tuple, list))
                else self.window)

    def layer_operator(self, i: int) -> str:
        return (self.operator[i] if isinstance(self.operator, (tuple, list))
                else self.operator)

    def layer_routed(self, i: int) -> bool:
        return bool(self.n_experts) and (self.routed is None
                                         or bool(self.routed[i]))

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def kv_groups(self) -> int:
        return self.n_heads // self.kv_heads


def init_params(rng: jax.Array, cfg: GPTConfig) -> Dict:
    """f32 parameter pytree.  Layout chosen so tensor-parallel sharding is
    a plain leading/trailing-axis split: q/k/v ``[D, H, Dh]`` (shard H;
    kv_heads under GQA), attention out ``[H, Dh, D]`` (shard H), MLP in
    ``[D, F]`` — or ``[D, F, 2]`` gate/up pairs under swiglu — / out
    ``[F, D]`` (shard F), LM head ``[D, V]`` (shard V)."""
    D, H, Dh, F, V = (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff,
                      cfg.vocab_size)
    Hkv = cfg.kv_heads
    k = iter(jax.random.split(
        rng, 4 + (7 if cfg.n_experts else 6) * cfg.n_layers))

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(fan_in))

    out_norms = ({"ln1_out": jnp.ones((D,), jnp.float32),
                  "ln2_out": jnp.ones((D,), jnp.float32)}
                 if cfg.out_norms else {})
    layers: List[Dict] = []
    G, Fe = cfg.held[1], cfg.d_expert
    for i in range(cfg.n_layers):
        layer = {**out_norms, "ln1": jnp.ones((D,), jnp.float32),
                 "ln2": jnp.ones((D,), jnp.float32)}
        if cfg.layer_operator(i) == "conv":
            # [b, c, v] in one product, the taps a channel, the output
            layer.update(w_in=dense(next(k), (D, 3 * D), D),
                         conv=dense(next(k), (CONV_TAPS, D), CONV_TAPS),
                         w_out=dense(next(k), (D, D), D))
        else:
            layer.update(wq=dense(next(k), (D, H, Dh), D),
                         wk=dense(next(k), (D, Hkv, Dh), D),
                         wv=dense(next(k), (D, Hkv, Dh), D),
                         wo=dense(next(k), (H, Dh, D), D))
            if cfg.qk_norm:
                layer.update(q_norm=jnp.ones((Dh,), jnp.float32),
                             k_norm=jnp.ones((Dh,), jnp.float32))
        if cfg.layer_routed(i):
            # the router over all experts, gate/up and down of those held
            layer.update(router=dense(next(k), (D, cfg.n_experts), D),
                         wi=dense(next(k), (G, D, 2 * Fe), D),
                         wm=dense(next(k), (G, Fe, D), Fe))
        else:
            layer.update(wi=dense(next(k), (D, F) if cfg.mlp == "gelu"
                                  else (D, 2, F), D),
                         wm=dense(next(k), (F, D), F))
        layers.append(layer)
    out = {
        "wte": dense(next(k), (V, D), D),
        "layers": layers,
        "lnf": jnp.ones((D,), jnp.float32),
        "lm_head": dense(next(k), (D, V), D),
    }
    if not cfg.rope:
        out["wpe"] = dense(next(k), (cfg.max_seq, D), D) * 0.1
    return out


def param_specs(cfg: GPTConfig, tp: Optional[str] = "tp") -> Dict:
    """PartitionSpec pytree matching :func:`init_params`.

    ``tp=None`` replicates everything (pure dp/sp)."""
    t = tp

    out_norms = {"ln1_out": P(), "ln2_out": P()} if cfg.out_norms else {}

    if cfg.n_experts:
        # experts are shared out by id (experts_held), never over tp
        ffn = {"router": P(), "wi": P(), "wm": P()}
    else:
        ffn = {"wi": P(None, t) if cfg.mlp == "gelu" else P(None, None, t),
               "wm": P(t, None)}

    def layer_specs():
        return {
            **out_norms,
            "ln1": P(),
            "wq": P(None, t, None),
            "wk": P(None, t, None),
            "wv": P(None, t, None),
            "wo": P(t, None, None),
            "ln2": P(),
            **ffn,
        }
    out = {
        "wte": P(),
        "layers": [layer_specs() for _ in range(cfg.n_layers)],
        "lnf": P(),
        "lm_head": P(None, t),
    }
    if not cfg.rope:
        out["wpe"] = P()
    return out


def validate_tp(cfg: GPTConfig, ntp: int) -> None:
    """Every dimension :func:`param_specs` shards over tp must divide by
    the rank count — the one validator shared by every tensor-parallel
    entry point (training, generation, the serving engine)."""
    if cfg.n_experts and ntp > 1:
        raise ValueError("a routed feed-forward is shared out by expert id "
                         "(experts_held), not over tensor-parallel ranks")
    for what, val in (("n_heads", cfg.n_heads), ("kv_heads", cfg.kv_heads),
                      ("d_ff", cfg.d_ff), ("vocab_size", cfg.vocab_size)):
        if val % ntp != 0:
            raise ValueError(f"{what}={val} not divisible by {ntp} "
                             f"tensor-parallel ranks")


def embed(params, tokens, pos, cfg: GPTConfig):
    """Token (+ learned position, unless RoPE) embedding.
    ``tokens`` [...,]; ``pos`` broadcastable positions."""
    with jax.named_scope("embed"):
        x = params["wte"][tokens]
        if not cfg.rope:
            x = x + params["wpe"][pos]
        return x.astype(cfg.dtype)


def rms_norm(x, scale, eps=1e-5):
    """RMS layernorm in f32 (bias-free); ``eps`` is ``GPTConfig.norm_eps``
    wherever a configuration is at hand.

    jax.checkpoint because the autodiff of the f32 upcast otherwise saves
    TWO f32 copies of the activation per call (the upcast and the
    normalized product — print_saved_residuals showed them dominating
    layer memory); recomputing the norm from ``x`` in the backward is two
    cheap bandwidth passes."""
    return _rms_norm(x, scale, eps)


@functools.partial(jax.checkpoint, static_argnums=(2,))
def _rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def _rope_rotate(t, pos, cfg: GPTConfig):
    """Rotary position embedding on [B, T, heads, Dh] with GLOBAL
    positions ``pos`` — [T] (shared across the batch; under sequence
    parallelism each shard rotates by its own global offsets, so
    ring/Ulysses attention needs no other change) or [B, T] (per-row
    positions — the continuous-batching decode path, where every slot
    sits at a different depth)."""
    half = cfg.head_dim // 2
    freqs = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * freqs  # [(B,) T, half]
    # angles/cos/sin in f32 (position precision); the big tensor math
    # runs in rope_dtype — default the activation dtype (an f32
    # round-trip on [B, T, H, Dh] costs two full extra HBM passes per
    # projection), opt-in f32 for long contexts (GPTConfig.rope_dtype)
    rd = cfg.rope_dtype or t.dtype
    # [(B,) T, 1, half] broadcasts over batch and heads either way
    cos = jnp.cos(ang)[..., None, :].astype(rd)
    sin = jnp.sin(ang)[..., None, :].astype(rd)
    t1, t2 = t[..., :half].astype(rd), t[..., half:].astype(rd)
    return jnp.concatenate([t1 * cos - t2 * sin,
                            t1 * sin + t2 * cos], axis=-1).astype(t.dtype)


def _layer_qkv(layer, x, cfg: GPTConfig, pos=None, rope=None,
               route=None):
    """ln1 + q/k/v projections — shared by the train and decode paths.
    Under GQA, k/v come out with ``kv_heads`` heads (the cache shape);
    use :func:`_expand_kv` before a full-width attend.  With
    ``cfg.qk_norm`` each head of q and k is RMS-normed first.  With RoPE,
    q/k are rotated here by the global positions ``pos``; ``rope`` says
    whether THIS layer rotates (default: the configuration's one value).

    ``route(layer, h)``: what reads the block's normed input beside the
    projections (an expert layer's router, placed before attention); its
    result comes back as a fourth value."""
    rope = cfg.layer_rope(0) if rope is None else rope
    if rope and pos is None:
        raise ValueError("RoPE model needs positions in _layer_qkv")
    with jax.named_scope("attn"):
        h = rms_norm(x, layer["ln1"], cfg.norm_eps)
        q = jnp.einsum("btd,dhk->bthk", h, layer["wq"].astype(cfg.dtype))
        kk = jnp.einsum("btd,dhk->bthk", h, layer["wk"].astype(cfg.dtype))
        v = jnp.einsum("btd,dhk->bthk", h, layer["wv"].astype(cfg.dtype))
        if cfg.qk_norm:
            q = rms_norm(q, layer["q_norm"], cfg.norm_eps)
            kk = rms_norm(kk, layer["k_norm"], cfg.norm_eps)
        if rope:
            q = _rope_rotate(q, pos, cfg)
            kk = _rope_rotate(kk, pos, cfg)
    if route is not None:
        return q, kk, v, route(layer, h)
    return q, kk, v


def _expand_kv(t, cfg: GPTConfig):
    """[B, T, kv_heads(/tp), Dh] -> [B, T, n_heads(/tp), Dh]: each KV
    head serves kv_groups query heads (single definition shared with the
    flash kernel's VJP so the repeat layout and its adjoint never
    drift)."""
    from ..ops.flash_attention import _expand_kv_heads
    return _expand_kv_heads(t, cfg.kv_groups)


def _dense_ffn(layer, h, cfg: GPTConfig, tp_axis: Optional[str] = None):
    """Post-norm activations -> FFN delta (no residual add)."""
    if cfg.mlp in ("swiglu", "reglu"):
        wi = layer["wi"].astype(cfg.dtype)          # [D, 2, F_local]
        fl = wi.shape[2]
        u = h @ wi.reshape(wi.shape[0], 2 * fl)     # one packed matmul
        gate = jax.nn.silu if cfg.mlp == "swiglu" else jax.nn.relu
        u = gate(u[..., :fl]) * u[..., fl:]
    else:
        u = jax.nn.gelu(h @ layer["wi"].astype(cfg.dtype))
    m = u @ layer["wm"].astype(cfg.dtype)
    if tp_axis:
        m = lax.psum(m, tp_axis)
    # named after the psum, so that a kept value is the reduced one
    return checkpoint_name(m, "ffn_proj")


def _routed_ffn(layer, h, routing, cfg: GPTConfig):
    """Post-norm activations -> the held experts' delta for the tokens
    ``routing`` (ids, weights: :func:`_route`) sends them."""
    from ..parallel.moe import expert_ffn
    B, T, D = h.shape
    with jax.named_scope("moe"):
        m = expert_ffn(h.reshape(B * T, D), *routing, layer["wi"],
                       layer["wm"], cfg.held, act=cfg.mlp)
    return m.reshape(B, T, D)


def _route(layer, h, cfg: GPTConfig, bias=None):
    """The expert layer's routing of the tokens ``h`` [B, T, D] (the
    output of the norm ``cfg.router_reads``): (ids, weights), each
    [B T, k]; ``bias`` on the sigmoid router's choice."""
    from ..parallel.moe import route_topk
    with jax.named_scope("moe"):
        return route_topk(h.reshape(-1, h.shape[-1]), layer["router"],
                          cfg.experts_per_token, score=cfg.router,
                          bias=bias)


def _layer_finish(layer, x, o, cfg: GPTConfig,
                  tp_axis: Optional[str] = None,
                  ffn: Optional[Any] = None,
                  remat_ffn: bool = False,
                  routing: Optional[Any] = None):
    """Attention output projection + residual + FFN — shared by the train
    and decode paths (any architecture change lands in both).

    ``ffn(layer, h) -> delta`` swaps the dense MLP for another FFN
    (e.g. switch-MoE) on the POST-norm activations; the residual add
    stays here so every GPT variant keeps the same block structure.

    ``remat_ffn`` checkpoints the norm+FFN sub-block: its internal
    activations (the [B, T, 2F] up-projection above all) are recomputed
    in the backward from ``x`` — the attention residuals stay saved.

    ``routing``: an expert layer's (ids, weights), made from the block's
    normed input, or ``_RouteHere`` (:func:`_ffn_residual`)."""
    with jax.named_scope("attn"):
        o = jnp.einsum("bthk,hkd->btd", o, layer["wo"].astype(cfg.dtype))
        if tp_axis:
            o = lax.psum(o, tp_axis)
        if cfg.out_norms:
            o = rms_norm(o, layer["ln1_out"], cfg.norm_eps)
        x = x + o
    return _ffn_residual(layer, x, cfg, tp_axis, ffn, remat_ffn, routing)


@functools.partial(jax.tree_util.register_dataclass, data_fields=["bias"],
                   meta_fields=[])
@dataclasses.dataclass(frozen=True)
class _RouteHere:
    """An expert layer whose router reads what the experts read (``ln2``):
    the routing is made inside the feed-forward, with ``bias`` on the
    choice, and the layer's result is ``(x, ids)``."""
    bias: Any = None


def _ffn_residual(layer, x, cfg: GPTConfig, tp_axis=None, ffn=None,
                  remat_ffn: bool = False, routing=None):
    """``x + FFN(ln2(x))``: the dense MLP, ``ffn``, or the held experts'
    with ``routing`` (see :func:`_layer_finish`)."""
    here = isinstance(routing, _RouteHere)

    def norm_ffn(layer, x, routing):
        h = rms_norm(x, layer["ln2"], cfg.norm_eps)
        if here:
            routing = _route(layer, h, cfg, routing.bias)
        if ffn is not None:
            m = ffn(layer, h)
        elif routing is not None:
            m = _routed_ffn(layer, h, routing, cfg)
        else:
            m = _dense_ffn(layer, h, cfg, tp_axis)
        if cfg.out_norms:
            m = rms_norm(m, layer["ln2_out"], cfg.norm_eps)
        return (m, routing[0]) if here else m

    if remat_ffn:
        norm_ffn = jax.checkpoint(norm_ffn)
    with jax.named_scope("ffn"):
        if here:
            m, ids = norm_ffn(layer, x, routing)
            return x + m, ids
        return x + norm_ffn(layer, x, routing)


def _short_conv(layer, x, cfg: GPTConfig):
    """The gated short convolution of a ``conv`` layer (LFM2) on ``x``
    [B, T, D], before the residual add: ``[b, c, v] = ln1(x) W_in``;
    ``z_t = sum_j conv[j] (b v)_{t-j}`` over the taps' positions,
    each channel its own taps, nought before the first position;
    ``(c z) W_out``.  The norm and the two projections stand under the
    scope ``conv_proj``, the gates and the taps (three shifted
    multiply-adds the compiler fuses, in float32) under ``conv``.  The two
    products' outputs are named ``conv_in`` and ``conv_out``, for
    ``remat="full"`` to keep (:data:`_FULL_REMAT_KEEPS`)."""
    T = x.shape[1]
    with jax.named_scope("conv_proj"):
        h = rms_norm(x, layer["ln1"], cfg.norm_eps)
        bcv = checkpoint_name(jnp.einsum(
            "btd,de->bte", h, layer["w_in"].astype(cfg.dtype)), "conv_in")
    with jax.named_scope("conv"):
        b, c, v = (t.astype(jnp.float32) for t in jnp.split(bcv, 3, -1))
        u, taps = b * v, layer["conv"].astype(jnp.float32)
        z = taps[0] * u
        for j in range(1, taps.shape[0]):
            z = z + taps[j] * jnp.pad(u, ((0, 0), (j, 0), (0, 0)))[:, :T]
        y = (c * z).astype(cfg.dtype)
    with jax.named_scope("conv_proj"):
        return checkpoint_name(jnp.einsum(
            "btd,de->bte", y, layer["w_out"].astype(cfg.dtype)), "conv_out")


def _attend(q, kk, v, attn: str, sp_axis: Optional[str],
            kv_groups: int = 1, window: Optional[int] = None):
    """``kk``/``v`` arrive COMPACT (kv_heads) under GQA: the sp paths
    transport them compact and expand at local compute (kv_groups-times
    less inter-chip KV traffic); local paths expand here.  ``window``:
    the local paths' sliding window (None: plain causal)."""
    if attn in ("ring", "ring_flash", "ulysses") and sp_axis is None:
        raise ValueError(f"attn={attn!r} needs a sequence-parallel axis")
    if window is not None and attn not in ("flash", "dense"):
        raise ValueError(f"attn={attn!r} has no sliding window: only the "
                         f"local paths (flash, dense) take one")
    if attn == "ring":
        return ring_attention(q, kk, v, sp_axis, causal=True,
                              kv_groups=kv_groups)
    if attn == "ring_flash":
        from ..parallel.ring_attention import ring_flash_attention
        return ring_flash_attention(q, kk, v, sp_axis, causal=True,
                                    kv_groups=kv_groups)
    if attn == "ulysses":
        return ulysses_attention(q, kk, v, sp_axis, causal=True,
                                 kv_groups=kv_groups)
    if attn == "flash":
        from ..ops.flash_attention import flash_attention
        return flash_attention(q, kk, v, causal=True, kv_groups=kv_groups,
                               window=window)
    if attn == "dense":
        from ..ops.flash_attention import _expand_kv_heads
        return reference_attention(q, _expand_kv_heads(kk, kv_groups),
                                   _expand_kv_heads(v, kv_groups),
                                   causal=True, window=window)
    raise ValueError(f"unknown attention mode {attn!r}")


def apply_layer(layer, x, cfg: GPTConfig, *,
                tp_axis: Optional[str] = None,
                sp_axis: Optional[str] = None,
                attn: str = "dense",
                ffn: Optional[Any] = None,
                pos=None,
                remat_ffn: bool = False,
                remat_around_attn: bool = False,
                index: int = 0,
                bias=None):
    """One transformer block on (local) activations ``x`` [B, T, D].
    ``pos`` [T]: GLOBAL token positions — required whenever the sequence
    is sharded (sp_axis) so RoPE rotates by global offsets; defaults to
    arange only in the unsharded case.  ``index``: which layer of the
    configuration this is, where layers differ (``cfg.rope``,
    ``cfg.window``, ``cfg.operator``, ``cfg.routed`` as tuples).

    A ``conv`` layer mixes its tokens with :func:`_short_conv` in place of
    attention.  Where the layer routes (``cfg.layer_routed``) the
    feed-forward is the held experts', reading the output of ``ln2``; the
    router reads ``cfg.router_reads``: the attention's input (computed
    here and handed on) or what the experts read.  ``bias`` ([n_experts],
    the sigmoid router's bias on the choice) makes the result ``(x,
    ids)``: the experts each token was sent to, [B T, k].

    ``remat_around_attn`` implements selective remat structurally: the
    qkv projections and the (output-projection + FFN) tail each sit in
    their own ``jax.checkpoint`` region while the attention op itself
    stays OUTSIDE any region — so its VJP residuals (q, k compact,
    v compact, out, lse) are saved across fwd→bwd and the backward never
    re-runs the attention kernel, while everything cheap to recompute
    (norms, projections, the [B, T, 2F] FFN blow-up) is rematerialized.
    """
    rope, window = cfg.layer_rope(index), cfg.layer_window(index)
    if pos is None:
        if rope and sp_axis is not None:
            raise ValueError("RoPE under sequence parallelism needs "
                             "explicit global positions (pos)")
        pos = jnp.arange(x.shape[1])
    routed = cfg.layer_routed(index) and ffn is None
    early = routed and cfg.router_reads == "ln1"
    if cfg.layer_operator(index) == "conv":
        if remat_around_attn or sp_axis is not None:
            raise ValueError("a conv layer has no attention to keep or "
                             "to share out: remat='attn' and sequence "
                             "parallelism are attention's")
        x = x + _short_conv(layer, x, cfg)
        out = _ffn_residual(layer, x, cfg, tp_axis, ffn, remat_ffn,
                            _RouteHere(bias) if routed else None)
        return out[0] if routed and bias is None else out

    qkv_fn = functools.partial(
        _layer_qkv, cfg=cfg, pos=pos, rope=rope,
        route=functools.partial(_route, cfg=cfg, bias=bias) if early
        else None)
    if remat_around_attn:
        qkv_fn = jax.checkpoint(qkv_fn)
    q, kk, v, *routing = qkv_fn(layer, x)
    with jax.named_scope("attn"):
        o = _attend(q, kk, v, attn, sp_axis, kv_groups=cfg.kv_groups,
                    window=window)

    finish = functools.partial(_layer_finish, cfg=cfg, tp_axis=tp_axis,
                               ffn=ffn, remat_ffn=remat_ffn)
    if remat_around_attn:
        finish = jax.checkpoint(finish)
    if early:
        out = finish(layer, x, o, routing=routing[0]), routing[0][0]
    elif routed:
        out = finish(layer, x, o, routing=_RouteHere(bias))
    else:
        out = finish(layer, x, o)
    return out[0] if routed and bias is None else out


def _local_attn(attn: str, T: int, sp_axis: Optional[str] = None) -> str:
    """What ``attn="auto"`` means for sequences of ``T`` on this backend."""
    if attn != "auto":
        return attn

    def _flash_ok():
        from ..ops.flash_attention import fit_block
        try:
            return fit_block(T, 512) >= 128  # tiny blocks lose to dense
        except ValueError:
            return False
    on_tpu = jax.default_backend() == "tpu"
    if sp_axis:
        return "ring_flash" if (on_tpu and _flash_ok()) else "ring"
    return "flash" if (on_tpu and _flash_ok()) else "dense"


# what remat="full" keeps of a layer beside its input: the flash kernel's
# output and lse (ops/flash_attention._fa_fwd), wm's output (_dense_ffn) and
# a conv layer's two products (_short_conv)
_FULL_REMAT_KEEPS = jax.checkpoint_policies.save_only_these_names(
    "ffn_proj", "flash_out", "flash_lse", "conv_in", "conv_out")


def layer_stack(params, tokens, cfg: GPTConfig, *,
                tp_axis: Optional[str] = None,
                sp_axis: Optional[str] = None,
                attn: str = "auto",
                remat: bool = False,
                router_bias=None):
    """``(x, run)``: the embedded tokens [B_local, T_local, D] and
    ``run(x) -> x``, one pass through every layer of ``params`` — what
    :func:`forward_features` does once and ``models/looped.py`` once a
    round.  Arguments as in :func:`forward_features`.

    ``router_bias``: the sigmoid router's bias on the choice, one
    [n_experts] array for each routed layer in order (``cfg.router``
    "sigmoid"); ``run(x)`` then returns ``(x, loads)``, ``loads``
    [routed layers, n_experts] int32: how many of the tokens' assignments
    each expert got (``parallel/moe.py`` ``held_assignments`` over all of
    them), what the train step moves the bias by.

    ``remat``: ``True`` or ``"full"`` puts one ``jax.checkpoint`` around
    each layer, which keeps the block's input and what a kernel or the
    FFN's output projection made (``_FULL_REMAT_KEEPS``) and makes the
    rest again in the backward.  What is kept follows what the traced
    block holds, with no switch: the flash output and its ``lse`` only
    where ``attn`` is the local flash kernel (the backward then runs no
    second ``flash_fwd``; dense, ring and Ulysses attention keep nothing
    new), ``wm``'s output only where the backward reads it
    (``cfg.out_norms``).  That is at most two ``[B, T, D]`` an attention
    layer visit beside the block's input, each ``B*T / (72*D)`` of the
    bytes of the layer's f32 weights with their Adam state (1.4% at D =
    4096 and 4,096 tokens a microbatch); most where weights are shared
    across rounds (``models/looped.py``).  A ``conv`` layer keeps the
    outputs of its two products, ``[B, T, 3D]`` and ``[B, T, D]``
    (:func:`_short_conv`), so its backward makes again the norm, the gates
    and the taps, and neither product.  A capacity knob for models that do
    not fit otherwise: a step pays for it with the layers' forward a second
    time.
    ``"ffn"`` and ``"attn"``: see :func:`apply_layer`."""
    T = tokens.shape[1]
    attn = _local_attn(attn, T, sp_axis)
    offset = lax.axis_index(sp_axis) * T if sp_axis else 0
    pos = offset + jnp.arange(T)

    x = embed(params, tokens, pos[None], cfg)

    if remat not in (True, "full", False, None, "", "none", "ffn", "attn"):
        raise ValueError(f"unknown remat mode {remat!r}")

    by_kind = {}

    def layer_fn(i):
        """One function for all the layers of layer ``i``'s kind: where
        every layer is of one kind, one function for all of them."""
        kind = (cfg.layer_operator(i), cfg.layer_rope(i),
                cfg.layer_window(i), cfg.layer_routed(i))
        if kind not in by_kind:
            fn = functools.partial(apply_layer, cfg=cfg, tp_axis=tp_axis,
                                   sp_axis=sp_axis, attn=attn, pos=pos,
                                   remat_ffn=(remat == "ffn"),
                                   remat_around_attn=(remat == "attn"),
                                   index=i)
            if remat in (True, "full"):
                fn = jax.checkpoint(fn, policy=_FULL_REMAT_KEEPS)
            by_kind[kind] = fn
        return by_kind[kind]

    def run(x):
        for i, layer in enumerate(params["layers"]):
            x = layer_fn(i)(layer, x)
        return x

    def run_with_loads(x):
        from ..parallel.moe import held_assignments
        biases, loads = iter(router_bias), []
        for i, layer in enumerate(params["layers"]):
            if cfg.layer_routed(i):
                x, ids = layer_fn(i)(layer, x, bias=next(biases))
                loads.append(held_assignments(ids, (0, cfg.n_experts)))
            else:
                x = layer_fn(i)(layer, x)
        return x, jnp.stack(loads)
    return x, (run if router_bias is None else run_with_loads)


def forward_features(params, tokens, cfg: GPTConfig, *,
                     tp_axis: Optional[str] = None,
                     sp_axis: Optional[str] = None,
                     attn: str = "auto",
                     remat: bool = False,
                     router_bias=None):
    """Transformer stack on this device's shard → post-norm features
    [B_local, T_local, D] (everything except the LM head); with
    ``router_bias`` (:func:`layer_stack`) ``(features, loads)``.  With an
    UNSHARDED head (no ``tp_axis``), feed these to
    ``ops.chunked_ce.chunked_cross_entropy`` to train without ever
    materializing [B, T, V] logits; under tensor parallelism use
    ``parallel_cross_entropy`` on the vocab-sharded logits instead.

    ``tokens``: [B_local, T_local] int32.  With ``sp_axis`` the global
    sequence is the rank-order concatenation of shards; with ``tp_axis``
    the head/feature dims hold the local slice and (in forward_local) the
    returned logits are vocab-sharded ``[B_local, T_local, V/tp]``.

    ``attn``: "ring" | "ring_flash" | "ulysses" (these need ``sp_axis``) |
    "flash" (Pallas kernel) | "dense"; "auto" = ring (flash-chunked on
    TPU) when sequence-parallel, else the flash kernel on TPU when the
    sequence tiles into its blocks (~1.5x dense throughput and no [T, T]
    materialization), else dense.
    """
    _one_round_only(cfg, "forward_features")
    x, run = layer_stack(params, tokens, cfg, tp_axis=tp_axis,
                         sp_axis=sp_axis, attn=attn, remat=remat,
                         router_bias=router_bias)
    if router_bias is None:
        x = run(x)
    else:
        x, loads = run(x)
    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["lnf"], cfg.norm_eps)
    return x if router_bias is None else (x, loads)


def _one_round_only(cfg: GPTConfig, what: str) -> None:
    if cfg.n_rounds != 1:
        raise ValueError(f"{what} runs the layers once; n_rounds="
                         f"{cfg.n_rounds} is trained by models/looped.py "
                         f"and has no decode path")


def _attention_layers_only(cfg: GPTConfig, what: str) -> None:
    """Paths that attend in every layer and route every layer or none, by
    softmax: refuse by name the short convolution, dense and routed
    feed-forwards in one stack, and the sigmoid router, which only
    ``layer_stack`` runs (with the bias that a train step moves)."""
    _one_round_only(cfg, what)
    if ({cfg.layer_operator(i) for i in range(cfg.n_layers)} != {"attn"}
            or cfg.routed is not None or cfg.router != "softmax"):
        raise ValueError(f"{what} attends in every layer over one kind of "
                         f"feed-forward; conv layers, dense and routed "
                         f"feed-forwards in one stack and the sigmoid "
                         f"router with its bias run through layer_stack "
                         f"only")


def _plain_layers_only(cfg: GPTConfig, what: str) -> None:
    """The decode paths, the cache and the pipeline walk layers of one
    kind, full causal attention over a dense feed-forward: refuse by name
    what they would compute wrongly."""
    _attention_layers_only(cfg, what)
    if (isinstance(cfg.rope, (tuple, list)) or cfg.window is not None
            or cfg.n_experts):
        raise ValueError(f"{what} walks layers of one kind, full causal "
                         f"attention over a dense feed-forward; a sliding "
                         f"window, layers of several kinds and a routed "
                         f"feed-forward run through layer_stack only")


def held_rows(params, tokens, cfg: GPTConfig, *, attn: str = "auto",
              router_bias=None):
    """A probe of an expert model: for each routed layer, how many of the
    tokens' assignments go to the experts held here, [routed layers]
    int32 — the rows its grouped products multiply in a forward over
    ``tokens`` [B, T] (``router_bias`` as in :func:`layer_stack`, where
    the router has one).  A forward of its own, for reading beside a
    training job, not inside."""
    if not cfg.n_experts:
        raise ValueError("held_rows reads an expert model (cfg.n_experts)")
    from ..parallel.moe import held_assignments
    T = tokens.shape[1]
    attn, pos = _local_attn(attn, T), jnp.arange(T)
    x = embed(params, tokens, pos[None], cfg)
    biases = iter(router_bias or ())
    counts = []
    for i, layer in enumerate(params["layers"]):
        if not cfg.layer_routed(i):
            x = apply_layer(layer, x, cfg, attn=attn, pos=pos, index=i)
            continue
        bias = next(biases, None)
        if cfg.router_reads == "ln1" and bias is None:
            ids, _ = _route(layer, rms_norm(x, layer["ln1"], cfg.norm_eps),
                            cfg)
            x = apply_layer(layer, x, cfg, attn=attn, pos=pos, index=i)
        else:
            x, ids = apply_layer(layer, x, cfg, attn=attn, pos=pos, index=i,
                                 bias=jnp.zeros(cfg.n_experts)
                                 if bias is None else bias)
        counts.append(jnp.sum(held_assignments(ids, cfg.held)))
    return jnp.stack(counts)


def forward_local(params, tokens, cfg: GPTConfig, *,
                  tp_axis: Optional[str] = None,
                  sp_axis: Optional[str] = None,
                  attn: str = "auto",
                  remat: bool = False):
    """``forward_features`` + LM head → logits (see forward_features for
    the sharding/attention contract)."""
    x = forward_features(params, tokens, cfg, tp_axis=tp_axis,
                         sp_axis=sp_axis, attn=attn, remat=remat)
    # f32 logits: the parallel cross-entropy reduces over the vocab shard
    return jnp.einsum("btd,dv->btv", x.astype(jnp.float32),
                      params["lm_head"])


def parallel_cross_entropy(logits_local, targets, *,
                           tp_axis: Optional[str] = None):
    """Token NLL with vocab-sharded logits.

    ``logits_local``: [B, T, V_local] f32; ``targets``: [B, T] *global*
    vocab ids.  The softmax normalizer and the target logit are assembled
    with one pmax + two psums over ``tp_axis`` — logits are never
    all-gathered (Megatron-style parallel cross-entropy).
    """
    v_local = logits_local.shape[-1]
    # the max is a numerical-stability shift that cancels in the result;
    # computing it on stop_gradient'ed logits keeps the exact softmax
    # gradient and keeps pmax (no differentiation rule) off the grad path
    m = jnp.max(lax.stop_gradient(logits_local), axis=-1)
    if tp_axis:
        m = lax.pmax(m, tp_axis)
    denom = jnp.sum(jnp.exp(logits_local - m[..., None]), axis=-1)
    lo = lax.axis_index(tp_axis) * v_local if tp_axis else 0
    local_t = targets - lo
    in_range = (local_t >= 0) & (local_t < v_local)
    safe = jnp.clip(local_t, 0, v_local - 1)
    picked = jnp.take_along_axis(logits_local, safe[..., None], -1)[..., 0]
    picked = jnp.where(in_range, picked, 0.0)
    if tp_axis:
        denom = lax.psum(denom, tp_axis)
        picked = lax.psum(picked, tp_axis)
    return m + jnp.log(denom) - picked  # [B, T]


def forward(params, tokens, cfg: GPTConfig):
    """Unsharded single-device forward → full logits (the oracle)."""
    return forward_local(params, tokens, cfg)


# --------------------------------------------------------------- generation
def init_kv_cache(cfg: GPTConfig, batch: int, max_len: Optional[int] = None):
    """Per-layer KV cache: k/v [B, max_len, kv_heads, Dh] in the model
    dtype (GQA stores only the KV heads — the cache shrinks by
    kv_groups)."""
    L = max_len or cfg.max_seq
    if L > cfg.max_seq and not cfg.rope:
        raise ValueError(f"cache length {L} exceeds max_seq {cfg.max_seq} "
                         f"(wpe has no embeddings past it; RoPE models "
                         f"have no such bound)")
    shape = (batch, L, cfg.kv_heads, cfg.head_dim)
    return [{"k": jnp.zeros(shape, cfg.dtype),
             "v": jnp.zeros(shape, cfg.dtype)}
            for _ in range(cfg.n_layers)]


def _decode_attend(q, kc, vc, pos):
    """q [B, 1, H, Dh] vs cache [B, L, H, Dh] (GQA callers repeat-expand
    the compact cache at the call site); positions > pos masked.  ``pos``
    is a scalar (whole batch at one depth — the plain generate loop) or
    [B] (each row at its own depth — the continuous-batching engine,
    serving/cache.py paged_decode_attend).

    NOTE on GQA bandwidth: the cache itself stays compact ([.., kv_heads,
    ..]); the repeat happens at this read and XLA fuses it into the
    attention without materializing the expansion — measured on v5e, the
    repeat form decodes ~25% FASTER than a 5-D grouped einsum that avoids
    the repeat symbolically (7.1k vs 5.6k tok/s at 12x1024, kv_heads=4),
    and 2.7x faster than MHA.  Don't "optimize" this into a grouped
    einsum without re-measuring."""
    L = kc.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   kc.astype(jnp.float32)) / np.sqrt(q.shape[-1])
    # scalar pos -> [1]; [B] pos stays — either broadcasts over the batch
    mask = (jnp.arange(L)[None, :]
            <= jnp.atleast_1d(pos)[:, None])[:, None, None, :]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      vc.astype(jnp.float32)).astype(q.dtype)


def _decode_hidden(params, cfg: GPTConfig, cache, pos, token,
                   tp_axis: Optional[str] = None):
    """One incremental step through the layer stack (no lm_head):
    ``(x_final [B, 1, D], new_cache)``.  Layer math is shared with the
    training path via _layer_qkv/_layer_finish; only the attend differs.
    Under ``tp_axis`` the cache and q/k/v hold the local head shard and
    the per-layer psums restore replicated activations — the same
    Megatron sharding as training."""
    _plain_layers_only(cfg, "_decode_hidden")
    x = embed(params, token[:, None], pos, cfg)               # [B, 1, D]
    pos1 = jnp.reshape(pos, (1,))
    new_cache = []
    for layer, kv in zip(params["layers"], cache):
        q, kk, v = _layer_qkv(layer, x, cfg, pos=pos1)
        kc = lax.dynamic_update_slice(kv["k"], kk, (0, pos, 0, 0))
        vc = lax.dynamic_update_slice(kv["v"], v, (0, pos, 0, 0))
        new_cache.append({"k": kc, "v": vc})
        with jax.named_scope("attn"):
            o = _decode_attend(q, _expand_kv(kc, cfg), _expand_kv(vc, cfg),
                               pos)
        x = _layer_finish(layer, x, o, cfg, tp_axis)
    with jax.named_scope("final_norm"):
        return rms_norm(x, params["lnf"], cfg.norm_eps), new_cache


def _head(params, x):
    """lm_head on [B, 1, D] → [B, V] f32 logits."""
    return jnp.einsum("btd,dv->btv", x.astype(jnp.float32),
                      params["lm_head"])[:, 0]


def tp_head(params, x, tp_axis: Optional[str] = None):
    """lm_head logits [B, V] f32 under optional tensor parallelism: the
    vocab-sharded local product (lm_head is ``P(None, tp)`` in
    :func:`param_specs`) is all-gathered over ``tp_axis`` — a tiny
    [B, V] f32 row — so every rank holds identical logits and any
    downstream argmax/sample picks the SAME token.  The one shared
    implementation for every tp decode path (parallel.threed generation,
    the serving engine)."""
    local = _head(params, x)
    if tp_axis is None:
        return local
    return lax.all_gather(local, tp_axis, axis=1, tiled=True)


def decode_step(params, cfg: GPTConfig, cache, pos, token):
    """One incremental decode step.

    ``token``: [B] int32 at position ``pos`` (scalar int32).  Returns
    ``(logits [B, V], new_cache)``.  Static shapes — jit/scan friendly.
    """
    x, cache = _decode_hidden(params, cfg, cache, pos, token)
    return _head(params, x), cache


def prefill(params, cfg: GPTConfig, cache, tokens,
            tp_axis: Optional[str] = None, head=None):
    """Fill the cache from a prompt [B, T] by running T incremental steps
    in a scan; returns (last_logits, cache).  The vocab-sized lm_head
    matmul runs ONCE, on the final hidden state — not inside the scan.
    ``head(x)`` overrides the logits head (e.g. the tp all-gathered one)."""
    T = tokens.shape[1]
    head = head or (lambda x: _head(params, x))

    def body(carry, t):
        cache, _ = carry
        x, cache = _decode_hidden(params, cfg, cache, t, tokens[:, t],
                                  tp_axis=tp_axis)
        return (cache, x), None

    z = jnp.zeros((tokens.shape[0], 1, cfg.d_model), cfg.dtype)
    (cache, x), _ = lax.scan(body, (cache, z), jnp.arange(T))
    return head(x), cache


def generate(params, cfg: GPTConfig, prompt, n_tokens: int,
             temperature: float = 0.0, rng: Optional[jax.Array] = None,
             max_len: Optional[int] = None, cache=None,
             tp_axis: Optional[str] = None, head=None):
    """Autoregressive generation (greedy, or sampled when temperature>0).

    ``prompt``: [B, T] int32.  Returns [B, n_tokens] int32.  The whole
    loop is one jittable scan over a static-shape KV cache.

    This is the ONLY decode loop — the tensor-parallel path
    (parallel.threed.make_tp_generate) calls it with a sharded ``cache``,
    ``tp_axis``, and an all-gathered ``head``, so sampling/cache changes
    land in both paths.
    """
    B, T = prompt.shape
    if cache is None:
        cache = init_kv_cache(cfg, B, max_len or cfg.max_seq)
    L = cache[0]["k"].shape[1]
    if L > cfg.max_seq and not cfg.rope:
        raise ValueError(f"cache length {L} exceeds max_seq {cfg.max_seq} "
                         f"(wpe has no embeddings past it; RoPE models "
                         f"have no such bound)")
    if T + n_tokens > L:
        raise ValueError(f"prompt {T} + {n_tokens} new tokens exceeds "
                         f"cache length {L}")
    head = head or (lambda x: _head(params, x))
    logits, cache = prefill(params, cfg, cache, prompt, tp_axis=tp_axis,
                            head=head)
    if rng is None:
        rng = jax.random.PRNGKey(0)

    def pick(logits, key):
        if temperature > 0:
            return jax.random.categorical(key, logits / temperature, axis=-1)
        return jnp.argmax(logits, axis=-1)

    def body(carry, i):
        cache, logits, key = carry
        key, sub = jax.random.split(key)
        tok = pick(logits, sub).astype(jnp.int32)
        x, cache = _decode_hidden(params, cfg, cache, T + i, tok,
                                  tp_axis=tp_axis)
        return (cache, head(x), key), tok

    (_, _, _), toks = lax.scan(body, (cache, logits, rng),
                               jnp.arange(n_tokens))
    return jnp.transpose(toks, (1, 0))  # [B, n_tokens]


def loss_fn(params, tokens, targets, cfg: GPTConfig):
    """Unsharded mean token NLL (the oracle)."""
    logits = forward(params, tokens, cfg)
    return parallel_cross_entropy(logits, targets).mean()
