"""Follow a training job's first steps with a family's plain reference.

One jitted step, with the weights, the optimizer's state and the batch as
arguments (a closure over weights would bake them into the program). What
comes back to the host is small: each step's loss, each leaf's gradient norm
at the first step and each leaf's norm of change after the last.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np

from . import optim

tmap = jax.tree_util.tree_map


def family(name: str):
    """The reference module of a configuration's `family`, found by name."""
    return importlib.import_module(f"perf.reference.{name}")


def leaf_norms(tree):
    """Euclidean norm of every leaf, in the tree's flattening order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


@functools.lru_cache(maxsize=None)
def _programs(family_name: str, config_key: str, opt_key: str, quant: str):
    import json
    fam, config, spec = (family(family_name), json.loads(config_key),
                         json.loads(opt_key))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, mstate, opt, t, batch):
        loss, grads, mstate = fam.loss_and_grads(params, mstate, batch,
                                                 config, quant)
        norms = leaf_norms(grads)
        params, opt = optim.update(params, grads, opt, t, spec)
        return params, mstate, opt, loss, norms

    @jax.jit
    def start(key):
        params = fam.init_params(key, config)
        return params, fam.init_model_state(config), optim.init(params, spec)

    @jax.jit
    def change(key, params, mstate):
        return (leaf_norms(tmap(jnp.subtract, params,
                                fam.init_params(key, config))),
                leaf_norms(tmap(jnp.subtract, mstate,
                                fam.init_model_state(config)))
                if jax.tree_util.tree_leaves(mstate) else jnp.zeros((0,)))

    return start, step, change


def follow(family_name: str, config: dict, optimizer: dict, key, batches,
           quant: str = "none", fault: str = "") -> dict:
    """Train from `key` over `batches` (host arrays, one per step).

    `fault` plants one of the faults a training cell can have, with this
    reference standing in the program's place: `half_batch` leaves the
    second half of every batch out and takes the mean over the rest.
    """
    import json
    start, step, change = _programs(
        family_name, json.dumps(config, sort_keys=True),
        json.dumps(optimizer, sort_keys=True), quant)
    params, mstate, opt = start(key)
    losses, grad_norms = [], None
    for t, batch in enumerate(batches, 1):
        if fault == "half_batch":
            batch = tmap(lambda a: a[:a.shape[0] // 2], batch)
        elif fault:
            raise ValueError(f"unknown fault {fault!r}")
        params, mstate, opt, loss, norms = step(
            params, mstate, opt, jnp.float32(t), tmap(jnp.asarray, batch))
        losses.append(loss)
        if t == 1:
            grad_norms = norms
    change_norms, state_norms = change(key, params, mstate)
    out = {"losses": [float(x) for x in losses],
           "grad_norms": np.asarray(grad_norms, np.float64),
           "change_norms": np.asarray(change_norms, np.float64),
           "state_norms": np.asarray(state_norms, np.float64)}
    del params, mstate, opt
    return out
