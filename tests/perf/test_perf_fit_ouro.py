"""The looped decoder's cell, its step compiled at its real size for a
described v5e chip (no chip attached, nothing runs), as
`tests/perf/test_perf_fit.py` does for the Mistral cell: the flash kernels
are in it at the size of one round, it fits the chip's memory, and the
state is donated. The topology is described inside a fixture, never at
import."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from perf.manifest import Manifest
from perf_testdata import ROOT

CELL = "ouro2.6b-train-4k"
CHIP_BYTES = 16_909_336_064     # `bytes_limit` of a v5e chip (PERF.md)


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
    from perf.adapters import ouro as adapter

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


def test_the_flash_kernels_are_in_the_step_once_a_layer_not_once_a_visit(
        compiled):
    # forward, delta, dq and dkv of 8 layers, and the forward again under
    # full remat: 40 calls inside the round loop's body, not 160
    calls = compiled.as_text().count("tpu_custom_call")
    assert 8 * 4 <= calls <= 8 * 5 + 8


def test_the_step_fits_the_chip(compiled):
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.generated_code_size_in_bytes
            + max(0, ma.output_size_in_bytes - ma.alias_size_in_bytes))
    assert need <= CHIP_BYTES, (
        f"{need / 1e9:.2f} GB: arguments {ma.argument_size_in_bytes / 1e9:.2f}"
        f", temporaries {ma.temp_size_in_bytes / 1e9:.2f}")
    # what the loaded executable holds at its peak is what the chip reports
    # (13.74e9 here, 14.07 GB on the chip; PERF.md section 4)
    peak = compiled.runtime_executable().get_compiled_memory_stats(
        ).peak_memory_in_bytes
    assert peak <= 0.9 * CHIP_BYTES, f"peak {peak / 1e9:.2f} GB"
    # the persistent state: f32 masters and AdamW's two moments of 612.4 M
    # weights (8 layers, the embedding, the head, the norms and the gate)
    assert ma.argument_size_in_bytes >= 12 * 612_000_000
    # and over a quarter of the chip, as a cell has to be
    assert need >= 0.25 * CHIP_BYTES


def test_the_state_is_donated(compiled):
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= 0.99 * ma.output_size_in_bytes
