"""What every family's adapter shares on the program's side: the mesh, the
state made on the device from the seed, the optimizer, and the small probes
that read the first gradient and the weights' change out of the program's
own state. The step itself comes from `kungfu_tpu.training`.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from perf.reference.train import leaf_norms

tmap = jax.tree_util.tree_map


@dataclasses.dataclass
class Job:
    """One cell's training job, as the loop drives it."""
    step: Callable        # (state, batch) -> (state, loss)
    lower: Callable       # (state shapes, batch shapes) -> jax.stages.Lowered
    init_state: Callable  # key -> state (params first, optimizer second)
    place: Callable       # host leaf -> device array, sharded over the mesh
    units_per_step: int
    optimizer: dict
    ref_family: Any
    config: dict
    probes: Any = None    # the loop's compiled readers of the first steps


def build(config: dict, traffic: dict, mesh) -> Job:
    """The job of a configuration's `family`, by its adapter found by name
    (perf/adapters/<family>.py)."""
    adapter = importlib.import_module("perf.adapters." + config["family"])
    return adapter.build(config, traffic, mesh)


def stack_sharding(mesh):
    return NamedSharding(mesh, P(mesh.axis_names))


def optimizer(spec: dict) -> optax.GradientTransformation:
    """The optax transformation a traffic file's `optimizer` names, under
    the program's synchronous-SGD wrapper."""
    import kungfu_tpu.optimizers as kfopt
    if spec["name"] == "adamw":
        base = optax.adamw(spec["learning_rate"], b1=spec["b1"],
                           b2=spec["b2"], eps=spec["eps"],
                           weight_decay=spec["weight_decay"])
    elif spec["name"] == "sgd":
        base = optax.sgd(spec["learning_rate"], momentum=spec["momentum"],
                         nesterov=spec["nesterov"])
    else:
        raise ValueError(f"unknown optimizer {spec['name']!r}")
    return kfopt.synchronous_sgd(base)


def stacked(make: Callable, mesh) -> Callable:
    """`make(key) -> tree` as one compiled program whose result is already
    lane-stacked and sharded, as `training.replicate` would leave it (which
    goes through the host: 2.8 GB each way for cell 1)."""
    n = mesh.devices.size
    return jax.jit(
        lambda key: tmap(lambda t: jnp.broadcast_to(t[None], (n,) + t.shape),
                         make(key)),
        out_shardings=stack_sharding(mesh))


def _find(state, name: str):
    """The first field called `name` in an optax state (nested tuples)."""
    if hasattr(state, name):
        return getattr(state, name)
    if isinstance(state, (tuple, list)):
        for sub in state:
            found = _find(sub, name)
            if found is not None:
                return found
    return None


@jax.jit
def _lane0_norms(tree):
    return leaf_norms(tmap(lambda t: t[0], tree))


def first_grad_norms(opt_state, spec: dict):
    """Each leaf's norm of the gradient the optimizer got at step 1, from
    its state after that step: Adam's first moment is (1 - b1) g then, and
    the momentum trace is g."""
    if spec["name"] == "adamw":
        return _lane0_norms(_find(opt_state, "mu")) / (1 - spec["b1"])
    if spec["name"] == "sgd":
        return _lane0_norms(_find(opt_state, "trace"))
    raise ValueError(f"unknown optimizer {spec['name']!r}")


def change_norms(make: Callable):
    """Program computing each leaf's norm of (tree now - tree made from the
    key): the start is made again from the seed, not kept."""
    return jax.jit(lambda key, tree: leaf_norms(
        tmap(lambda now, was: now[0] - was, tree, make(key))))
