"""The Mistral cell's step, compiled at its real size for a described v5e
chip (no chip attached, nothing runs): the flash kernels are in it and it
fits the chip's memory. The only test file that loads the TPU's compiler;
the topology is described inside a fixture, never at import."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from perf.manifest import Manifest
from perf.peaks import peaks_for
from perf_testdata import ROOT

CELL = "mistral7b-train-4k"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # whatever keeps the compiler from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compiled(topo):
    from kungfu_tpu.comm.mesh import PEER_AXIS
    from kungfu_tpu.ops import flash_attention
    from perf.adapters import gpt as adapter

    cell = Manifest(ROOT).cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    mesh = Mesh(np.array(topo.devices[:1]), (PEER_AXIS,))
    sharding = NamedSharding(mesh, P(mesh.axis_names))
    with pytest.MonkeyPatch.context() as patch:
        # the kernels ask jax.default_backend(), which is the CPU here, and
        # would take their interpret branch: steer them to the chip's
        patch.setattr(flash_attention, "_auto_interpret", lambda: False)
        job = adapter.build(config, traffic, mesh)
        state = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=sharding),
            jax.eval_shape(job.init_state, jax.random.PRNGKey(0)))
        tokens = jax.ShapeDtypeStruct(
            (traffic["batch"], traffic["seq_len"]), jnp.int32,
            sharding=sharding)
        return job.lower(state, (tokens, tokens)).compile()


def test_the_flash_kernels_are_in_the_step(compiled):
    # forward and backward (dq, dkv, delta) of two layers, and the forward
    # again under full remat
    assert compiled.as_text().count("tpu_custom_call") >= 8


def test_the_step_fits_the_chip(compiled):
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.generated_code_size_in_bytes
            + max(0, ma.output_size_in_bytes - ma.alias_size_in_bytes))
    assert need <= peaks_for("TPU v5 lite")["hbm_bytes"], (
        f"{need / 1e9:.2f} GB: arguments {ma.argument_size_in_bytes / 1e9:.2f}"
        f", temporaries {ma.temp_size_in_bytes / 1e9:.2f}")
    # the persistent state alone is over half the chip: f32 masters and
    # AdamW's two moments of 698.4 M weights
    assert ma.argument_size_in_bytes >= 12 * 698_000_000


def test_the_state_is_donated(compiled):
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= 0.99 * ma.output_size_in_bytes
