"""MoE-GPT: the GPT family with switch-MoE FFN layers, trained dp x ep.

Every ``expert_every``-th transformer block swaps its dense MLP for a
switch-MoE FFN (parallel/moe.py): top-1 routing with static capacity,
experts sharded over the ``ep`` mesh axis, tokens exchanged with two
all_to_alls.  Outside the expert blocks both dp and ep act as data axes
(the batch is sharded over dp x ep jointly), so the non-expert gradients
psum over both via shard_map's varying-axis AD while expert gradients
psum over dp only — no hand-written synchronization, same design as
threed.py.

The reference framework has neither MoE nor any model-partitioning axis
(SURVEY.md §2.4); this composes the framework's EP extension with the GPT
family end-to-end.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import gpt as G
from . import moe as M

DP_AXIS, EP_AXIS = "dp", "ep"


@dataclasses.dataclass(frozen=True)
class MoEGPTConfig:
    gpt: G.GPTConfig
    n_experts: int = 8
    expert_every: int = 2          # every k-th layer is MoE (the last of k)
    capacity_factor: float = 1.25
    aux_weight: float = 0.01

    def is_moe_layer(self, i: int) -> bool:
        return i % self.expert_every == self.expert_every - 1

    @property
    def moe(self) -> M.MoEConfig:
        return M.MoEConfig(d_model=self.gpt.d_model, d_ff=self.gpt.d_ff,
                           n_experts=self.n_experts,
                           capacity_factor=self.capacity_factor,
                           dtype=self.gpt.dtype)


def init_params(rng: jax.Array, cfg: MoEGPTConfig):
    """Dense GPT params with MoE layers' MLPs replaced by expert banks."""
    base = G.init_params(rng, cfg.gpt)
    keys = jax.random.split(jax.random.fold_in(rng, 1), cfg.gpt.n_layers)
    layers = []
    for i, layer in enumerate(base["layers"]):
        if cfg.is_moe_layer(i):
            layer = {k: v for k, v in layer.items()
                     if k not in ("wi", "wm")}
            layer["moe"] = M.init_moe_params(keys[i], cfg.moe)
        layers.append(layer)
    out = dict(base)
    out["layers"] = layers
    return out


def param_specs(cfg: MoEGPTConfig, ep: Optional[str] = EP_AXIS):
    base = G.param_specs(cfg.gpt, tp=None)
    layers = []
    for i, spec in enumerate(base["layers"]):
        if cfg.is_moe_layer(i):
            spec = {k: v for k, v in spec.items() if k not in ("wi", "wm")}
            spec["moe"] = M.moe_param_specs(ep)
        layers.append(spec)
    out = dict(base)
    out["layers"] = layers
    return out


def forward_local(params, tokens, cfg: MoEGPTConfig,
                  ep_axis: Optional[str] = None, attn: str = "dense"):
    """Local forward → (logits [B, T, V], mean aux loss).  Without
    ``ep_axis`` each rank holds all experts (the oracle)."""
    g = cfg.gpt
    G._one_round_only(g, "moe_gpt.forward_local")
    T = tokens.shape[1]
    pos = jnp.arange(T)
    x = G.embed(params, tokens, pos[None], g)

    # both layer kinds run through gpt.apply_layer (same attention dispatch
    # and block structure); MoE layers just plug a different FFN in
    aux_acc = []

    def moe_ffn_cb(layer, h):
        y, aux = M.moe_ffn(layer["moe"], h, cfg.moe, ep_axis=ep_axis,
                           residual=False)
        aux_acc.append(aux)
        return y

    for layer in params["layers"]:
        ffn = moe_ffn_cb if "moe" in layer else None
        x = G.apply_layer(layer, x, g, attn=attn, ffn=ffn, pos=pos)
    x = G.rms_norm(x, params["lnf"], g.norm_eps)
    logits = jnp.einsum("btd,dv->btv", x.astype(jnp.float32),
                        params["lm_head"])
    aux_total = (sum(aux_acc) / len(aux_acc)) if aux_acc else jnp.float32(0.)
    return logits, aux_total


def mesh_dp_ep(dp: int, ep: int,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    from ..comm.mesh import make_mesh
    return make_mesh((DP_AXIS, EP_AXIS), (dp, ep), devices)


def shard_params(params, cfg: MoEGPTConfig, mesh: Mesh):
    specs = param_specs(cfg)
    return jax.tree_util.tree_map(
        lambda t, s: jax.device_put(t, NamedSharding(mesh, s)), params, specs)


def make_train_step(cfg: MoEGPTConfig,
                    optimizer: optax.GradientTransformation,
                    mesh: Mesh, attn: str = "dense",
                    donate: bool = True) -> Callable:
    """Compile ``step(params, opt_state, tokens, targets) -> (params,
    opt_state, loss)`` over a (dp, ep) mesh; batch sharded over dp x ep."""
    specs = param_specs(cfg)
    data_spec = P((DP_AXIS, EP_AXIS), None)

    def grad_body(params, tokens, targets):
        total = (tokens.shape[0] * tokens.shape[1]
                 * lax.axis_size(DP_AXIS) * lax.axis_size(EP_AXIS))

        def local_loss(p):
            logits, aux = forward_local(p, tokens, cfg, ep_axis=EP_AXIS,
                                        attn=attn)
            nll = G.parallel_cross_entropy(logits, targets)
            # aux is already pmean'd over ep inside moe_ffn
            aux = lax.pmean(aux, DP_AXIS)
            return nll.sum() / total + cfg.aux_weight * aux / (
                lax.axis_size(DP_AXIS) * lax.axis_size(EP_AXIS))

        lval, grads = jax.value_and_grad(local_loss)(params)
        loss = lax.psum(lval, (DP_AXIS, EP_AXIS))
        return loss, grads

    sm = jax.shard_map(grad_body, mesh=mesh,
                       in_specs=(specs, data_spec, data_spec),
                       out_specs=(P(), specs))

    def step(params, opt_state, tokens, targets):
        loss, grads = sm(params, tokens, targets)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    kwargs = {"donate_argnums": (0, 1)} if donate else {}
    return jax.jit(step, **kwargs)


def init_moe_gpt(cfg: MoEGPTConfig, optimizer, mesh: Mesh, seed: int = 0):
    params = shard_params(init_params(jax.random.PRNGKey(seed), cfg),
                          cfg, mesh)
    opt_state = jax.jit(optimizer.init)(params)
    return params, opt_state
