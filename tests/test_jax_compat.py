"""Guard the jax-internal surfaces this framework leans on.

The repo pins jax in requirements-ci.txt, but the compat workflow
(.github/workflows/compat.yaml — the analogue of the reference's
framework-version matrix, .github/workflows/compatiability.yaml) also
runs against newest jax.  These assertions turn "an internal moved and
the distributed plane broke silently" into a pointed failure naming
the surface and its user.
"""
import jax


def test_private_distributed_state_surface():
    """kungfu_tpu.distributed.shutdown() force-resets jax's distributed
    global state after unclean peer deaths (distributed.py)."""
    from jax._src import distributed as _dist
    assert hasattr(_dist, "global_state")
    assert hasattr(_dist.global_state, "client")
    # the reset path constructs a fresh State()
    assert callable(_dist.State)


def test_backend_clear_surface():
    """distributed._clear_backends() drops XLA backends between cluster
    versions (a reinit must rebuild the device set)."""
    import jax.extend.backend as _eb
    assert callable(_eb.clear_backends)
    from jax._src import xla_bridge
    assert callable(xla_bridge.backends_are_initialized)


def test_distributed_initialize_kwargs():
    """distributed.initialize() passes elastic-tuned heartbeat/shutdown
    timeouts; jax renaming these kwargs would break every resize."""
    import inspect
    sig = inspect.signature(jax.distributed.initialize)
    for kw in ("coordinator_address", "num_processes", "process_id",
               "local_device_ids", "heartbeat_timeout_seconds",
               "shutdown_timeout_seconds"):
        assert kw in sig.parameters, f"jax.distributed.initialize lost {kw}"


def test_recoverability_flags():
    """initialize() relies on recoverable mode (peer death -> catchable
    error) and on disabling jax's preemption SIGTERM trap."""
    for flag in ("jax_enable_recoverability",
                 "jax_enable_preemption_service"):
        assert flag in jax.config.values, f"jax.config lost {flag}"


def test_current_names_are_native():
    """The package aliases nothing onto jax: shard_map, typeof, the
    static axis size and pcast are called by their current names
    (training.py, ops/flash_attention.py, parallel/)."""
    for mod, name in ((jax, "shard_map"), (jax, "typeof"),
                      (jax.lax, "axis_size"), (jax.lax, "pcast")):
        fn = getattr(mod, name)
        assert callable(fn)
        assert fn.__module__.startswith("jax"), (name, fn.__module__)


def test_shard_map_and_array_assembly():
    """The sharded elastic path builds global arrays from per-device
    chunks and shard_maps every step."""
    assert callable(jax.shard_map)
    assert callable(jax.make_array_from_single_device_arrays)
    import jax.numpy as jnp
    arr = jnp.arange(4)
    shards = arr.addressable_shards
    assert shards and hasattr(shards[0], "index")
    assert hasattr(shards[0], "data")


# ------------------------------------------------- cost-analysis shim
def test_cost_analysis_shim_shapes():
    """compiled_cost_analysis (kfprof flops/HBM gauges): the installed
    jax's plain dict passes through; a missing attribute, a raising
    backend or any other return shape answers None."""
    from kungfu_tpu.utils.jax_compat import compiled_cost_analysis

    class DictStyle:
        def cost_analysis(self):
            return {"flops": 2.0, "bytes accessed": 4.0}

    class ListStyle:
        def cost_analysis(self):
            return [{"flops": 3.0, "bytes accessed": 6.0}]

    class Raises:
        def cost_analysis(self):
            raise NotImplementedError("no cost model on this backend")

    class NoAttr:
        pass

    assert compiled_cost_analysis(DictStyle()) == {
        "flops": 2.0, "bytes accessed": 4.0}
    assert compiled_cost_analysis(ListStyle()) is None
    assert compiled_cost_analysis(Raises()) is None
    assert compiled_cost_analysis(NoAttr()) is None


def test_cost_analysis_real_jit():
    """This jax's real AOT Compiled must yield a flops count for a
    matmul (the gauge the roofline fraction divides by)."""
    import jax.numpy as jnp
    from kungfu_tpu.utils.jax_compat import compiled_cost_analysis
    fn = jax.jit(lambda x: x @ x)
    compiled = fn.lower(jnp.ones((16, 16), jnp.float32)).compile()
    cost = compiled_cost_analysis(compiled)
    assert isinstance(cost, dict)
    assert float(cost.get("flops", 0.0)) > 0


def test_cost_analysis_survives_donation():
    """A donated step (elastic/trainer.py ships donate=True) must still
    yield cost gauges: lower_for_cost_analysis strips donation by
    lowering a non-donated twin, and the twin's lowering declares no
    donated arguments."""
    import jax.numpy as jnp
    from kungfu_tpu.utils.jax_compat import (compiled_cost_analysis,
                                             lower_for_cost_analysis)
    fn = jax.jit(lambda x, y: (x @ y, x + y), donate_argnums=(0, 1))
    x = jnp.ones((16, 16), jnp.float32)
    lowered = lower_for_cost_analysis(fn, x, x)
    infos = jax.tree_util.tree_leaves(
        lowered.args_info, is_leaf=lambda a: hasattr(a, "donated"))
    assert not any(getattr(i, "donated", False) for i in infos)
    cost = compiled_cost_analysis(lowered.compile())
    if cost is not None:
        assert float(cost.get("flops", 0.0)) > 0


def test_lower_for_cost_analysis_fake_fallback():
    """Objects without args_info/__wrapped__ (the test fakes) must
    route through fn.lower unchanged."""
    from kungfu_tpu.utils.jax_compat import lower_for_cost_analysis

    class Fake:
        def lower(self, *a, **k):
            return self

    f = Fake()
    assert lower_for_cost_analysis(f) is f


def test_cost_gauges_absent_when_shim_says_none(monkeypatch):
    """publish_compiled_cost on a backend without a cost model: no
    gauges, no crash."""
    from kungfu_tpu.monitor import Monitor
    from kungfu_tpu.monitor import profiler as prof

    class NoCost:
        def lower(self, *a, **k):
            return self

        def compile(self):
            return object()      # no cost_analysis attribute

    mon = Monitor()
    assert prof.publish_compiled_cost(NoCost(), monitor=mon) is None
    assert "kungfu_tpu_step_flops" not in mon.render_metrics()


def test_cost_republish_after_rebuild(monkeypatch):
    """The elastic trainers re-arm _cost_published in _build, so a
    resize re-publishes the gauges for the new program — prove the
    one-shot flag semantics both ways."""
    from kungfu_tpu.monitor import Monitor
    from kungfu_tpu.monitor import profiler as prof

    calls = []

    class Costed:
        def __init__(self, flops):
            self.flops = flops

        def lower(self, *a, **k):
            return self

        def compile(self):
            calls.append(self.flops)
            return self

        def cost_analysis(self):
            return {"flops": self.flops, "bytes accessed": 1.0}

    mon = Monitor()
    out1 = prof.publish_compiled_cost(Costed(100.0), monitor=mon)
    assert out1 == {"flops": 100.0, "hbm_bytes": 1.0}
    # "resize": a new program re-publishes and overwrites the gauge
    out2 = prof.publish_compiled_cost(Costed(900.0), monitor=mon)
    assert out2 == {"flops": 900.0, "hbm_bytes": 1.0}
    assert calls == [100.0, 900.0]
    assert "kungfu_tpu_step_flops 900" in mon.render_metrics()
