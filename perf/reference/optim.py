"""The optimizers' update rules, written out. State is a dict of trees.

`adamw`: Loshchilov and Hutter, arXiv:1711.05101, with the bias correction
of Adam and the decay added to the step before the learning rate, over
every leaf. `sgd`: momentum in Nesterov's form as Sutskever et al. (2013)
give it: the trace is g + m * trace, the step is g + m * trace'.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

tmap = jax.tree_util.tree_map


def init(params, spec: dict) -> dict:
    zeros = lambda: tmap(jnp.zeros_like, params)
    if spec["name"] == "adamw":
        return {"mu": zeros(), "nu": zeros()}
    if spec["name"] == "sgd":
        return {"trace": zeros()}
    raise ValueError(f"no reference for optimizer {spec['name']!r}")


def update(params, grads, state: dict, t, spec: dict):
    """Step number `t` (from 1). Returns (params, state)."""
    lr = spec["learning_rate"]
    if spec["name"] == "adamw":
        b1, b2, eps, wd = (spec["b1"], spec["b2"], spec["eps"],
                           spec["weight_decay"])
        mu = tmap(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
        nu = tmap(lambda v, g: b2 * v + (1 - b2) * g * g, state["nu"], grads)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        params = tmap(
            lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                      + wd * p), params, mu, nu)
        return params, {"mu": mu, "nu": nu}
    if spec["name"] == "sgd":
        mom = spec["momentum"]
        trace = tmap(lambda tr, g: g + mom * tr, state["trace"], grads)
        step = (tmap(lambda g, tr: g + mom * tr, grads, trace)
                if spec["nesterov"] else trace)
        return tmap(lambda p, u: p - lr * u, params, step), {"trace": trace}
    raise ValueError(f"no reference for optimizer {spec['name']!r}")
