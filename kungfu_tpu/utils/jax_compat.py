"""Helpers over the jax surfaces the kfprof cost gauges use.

The repo runs on the one jax the image installs (0.9.0:
``jax.shard_map``, ``jax.typeof``, ``lax.axis_size``, ``lax.pcast``,
the recoverable-distributed config flags, a dict from
``cost_analysis()`` — pinned by tests/test_jax_compat.py), so nothing
here aliases or feature-tests a jax name.  What remains is behaviour:
lowering a donating step without its donation, and a cost analysis that
answers None where a backend has no cost model.
"""
from __future__ import annotations


def lower_for_cost_analysis(fn, *args, **kwargs):
    """AOT-lower ``fn(*args, **kwargs)`` for cost analysis, stripping
    buffer donation (publish_compiled_cost, monitor/profiler.py).

    A donating step compiles to a program whose donated inputs alias
    its outputs, so ``cost_analysis()`` under-counts "bytes accessed" —
    and the throwaway AOT compile emits donation warnings (or, on some
    jaxlib builds, refuses) for buffers that are never actually
    executed.  When the lowering declares donated arguments (probed
    through ``Lowered.args_info``; absent means not donating), re-jit
    the wrapped function with donation off and lower that twin
    instead.  Falls back to the original lowering when
    the twin cannot be built (no ``__wrapped__``, e.g. a fake in
    tests), so the gauges never regress for non-donating callers."""
    import jax
    lowered = fn.lower(*args, **kwargs)
    try:
        infos = jax.tree_util.tree_leaves(
            lowered.args_info, is_leaf=lambda x: hasattr(x, "donated"))
        donating = any(getattr(i, "donated", False) for i in infos)
    except Exception:
        donating = False
    if not donating:
        return lowered
    inner = getattr(fn, "__wrapped__", None)
    if inner is None:
        return lowered
    try:
        return jax.jit(inner).lower(*args, **kwargs)
    except Exception:
        return lowered


def compiled_cost_analysis(compiled) -> "dict | None":
    """XLA cost analysis of an AOT-compiled step (the kfprof flops/HBM
    gauges, monitor/profiler.py): one flat ``{"flops": ..., "bytes
    accessed": ..., ...}`` dict, or None when the object has no
    ``cost_analysis`` or the backend has no cost model — absence of the
    gauges, never a crash (tests/test_jax_compat.py)."""
    fn = getattr(compiled, "cost_analysis", None)
    if fn is None:
        return None
    try:
        cost = fn()
    except Exception:
        # backends without a cost model raise from deep inside xla
        # (NotImplementedError, XlaRuntimeError, ...): "unknown" is an
        # expected answer here, not a failure to surface
        return None
    if not isinstance(cost, dict):
        return None
    return dict(cost)
