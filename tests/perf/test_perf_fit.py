"""Every cell whose traffic states a `remat`, its step compiled at its real
size for a described v5e chip (no chip attached, nothing runs), and held to
what a cell must be, not to the program's present shape:

1. the flash kernels are in the step, once a layer (not once a visit);
2. the step is inside the chip, by the loaded executable's peak, which is
   what the chip will report;
3. the cell is large enough to stand for a deployment;
4. the state is whole (f32 masters and AdamW's two moments) and donated;
5. the rules can still say no.

Which pass runs a kernel again, and what the compiler keeps between the
passes, is the program's to choose. The only test file that loads the TPU's
compiler; the topology is described inside a fixture, never at import."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from perf.manifest import Manifest
from perf_testdata import ROOT, copy_data

# `bytes_limit` of a v5e chip as jax reports it there (PERF.md section 4)
CHIP_BYTES = 16_909_336_064
INSIDE, LARGE_ENOUGH = 0.9, 0.25    # shares of CHIP_BYTES: rules 2 and 3
# a cell whose step does not pass rule 2 without its `remat` (PERF.md
# section 4: the compiler refuses it at 19.42 G of 15.75 G)
NEEDS_ITS_REMAT = "ouro2.6b-train-4k"


def cells_that_state_a_remat(manifest: Manifest) -> list:
    out = []
    for name, workload in manifest.cells.items():
        with open(os.path.join(manifest.root, "perf", "traffic",
                               workload["traffic"] + ".json")) as f:
            if json.load(f).get("remat"):
                out.append(name)
    return out


FIT_CELLS = cells_that_state_a_remat(Manifest(ROOT))


def kernel_problems(text: str, layers: int) -> list:
    """What rule 1 finds wrong in a compiled step's text; empty when the
    kernels are in it once a layer: a forward and a backward kernel a layer
    at the least; the delta, dq and dkv kernels and one forward more a
    layer, and a layer's worth outside a loop, at the most (a looped model
    that unrolls its rounds holds `4 * layers * rounds`). A kernel's `name=`
    stands in the `op_name` of its call's metadata, on the call's line."""
    calls = [line.split("metadata=", 1)[-1] for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    out = [f"{n} {name}* calls for {layers} layers"
           for name in ("flash_fwd", "flash_bwd_")
           if (n := sum(name in call for call in calls)) < layers]
    if len(calls) > 6 * layers:
        out.append(f"{len(calls)} Mosaic calls for {layers} layers: over "
                   f"{6 * layers}, which is once a visit and not once a layer")
    return out


def peak_bytes(compiled) -> int:
    """What the loaded executable holds at its peak: 0.8 to 2.4% under what
    the chip reports (13.877e9 against 13,988,884,480 for Mistral's step,
    13.736e9 against 14,061,594,112 for Ouro's; PERF.md section 4)."""
    return compiled.runtime_executable().get_compiled_memory_stats(
        ).peak_memory_in_bytes


def inside_the_chip(peak: int) -> bool:
    return peak <= INSIDE * CHIP_BYTES


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # whatever keeps the compiler from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def compile_step(topo, cell: dict):
    """`cell`'s step (a cell as `Manifest.cell` gives it) and the job it
    was lowered from."""
    from kungfu_tpu.comm.mesh import PEER_AXIS
    from kungfu_tpu.ops import flash_attention
    from perf import program

    config, traffic = cell["config"], cell["traffic"]
    mesh = Mesh(np.array(topo.devices[:1]), (PEER_AXIS,))
    sharding = NamedSharding(mesh, P(mesh.axis_names))
    with pytest.MonkeyPatch.context() as patch:
        # the kernels ask jax.default_backend(), which is the CPU here, and
        # would take their interpret branch: steer them to the chip's
        patch.setattr(flash_attention, "_auto_interpret", lambda: False)
        job = program.build(config, traffic, mesh)
        state = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=sharding),
            jax.eval_shape(job.init_state, jax.random.PRNGKey(0)))
        tokens = jax.ShapeDtypeStruct(
            (traffic["batch"], traffic["seq_len"]), jnp.int32,
            sharding=sharding)
        return job.lower(state, (tokens, tokens)).compile(), job


@pytest.fixture(scope="module", params=FIT_CELLS)
def fit(request, topo):
    """(a cell's compiled step, its job): one compile a cell."""
    return compile_step(topo, Manifest(ROOT).cell(request.param))


def test_the_flash_kernels_are_in_the_step_once_a_layer(fit):
    compiled, job = fit
    # the file's layers, never times the rounds: a loop over rounds holds
    # its kernels once
    layers = job.config["num_hidden_layers"]
    assert not kernel_problems(compiled.as_text(), layers)


def test_the_step_is_inside_the_chip(fit):
    peak = peak_bytes(fit[0])
    assert inside_the_chip(peak), (
        f"peak {peak / 1e9:.3f} GB over {INSIDE} of {CHIP_BYTES / 1e9:.3f}")


def test_the_cell_is_large_enough(fit):
    peak = peak_bytes(fit[0])
    assert peak >= LARGE_ENOUGH * CHIP_BYTES, f"peak {peak / 1e9:.3f} GB"


def test_the_state_is_whole(fit):
    # f32 masters and AdamW's two moments of every weight the plain
    # reference counts (698.4 M for Mistral's two layers, 612.4 M for Ouro's
    # eight), whatever the step keeps beside them
    compiled, job = fit
    weights = sum(leaf.size for leaf in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda key: job.ref_family.init_params(key, job.config),
                       jax.random.PRNGKey(0))))
    assert compiled.memory_analysis().argument_size_in_bytes >= 12 * weights


def test_the_state_is_donated(fit):
    ma = fit[0].memory_analysis()
    assert ma.alias_size_in_bytes >= 0.99 * ma.output_size_in_bytes


def test_a_step_without_its_remat_is_not_inside_the_chip(topo):
    cell = Manifest(ROOT).cell(NEEDS_ITS_REMAT)
    del cell["traffic"]["remat"]
    try:
        compiled, _ = compile_step(topo, cell)
    except jax.errors.JaxRuntimeError as e:
        # the compiler's own refusal is rule 2's no
        assert "RESOURCE_EXHAUSTED" in str(e), e
        return
    assert not inside_the_chip(peak_bytes(compiled))


def _text(fwd: int, bwd: int) -> str:
    """A compiled step's text as far as rule 1 reads it."""
    call = ('%c = bf16[1] custom-call(), custom_call_target='
            '"tpu_custom_call", metadata={{op_name="jit(step)/{}"}}\n')
    return call.format("flash_fwd") * fwd + call.format("flash_bwd_dkv") * bwd


@pytest.mark.parametrize("fwd, bwd, layers, problems", [
    # full remat today: the forward twice, delta and the fused backward
    (16, 16, 8, 0), (4, 4, 2, 0),
    # no forward again: what a checkpoint policy may leave
    (8, 16, 8, 0), (2, 4, 2, 0),
    # the least and the most
    (8, 8, 8, 0), (16, 32, 8, 0),
    # once a visit: eight layers unrolled over four rounds, with four
    # kernels a visit and with five
    (64, 64, 8, 1), (64, 96, 8, 1), (32, 64, 8, 1),
    # a layer without its kernel, a pass without its kernel, no kernel
    (7, 8, 8, 1), (8, 7, 8, 1), (0, 16, 8, 1), (16, 0, 8, 1), (0, 0, 8, 2),
])
def test_the_count_of_kernels_says_yes_and_no(fwd, bwd, layers, problems):
    assert len(kernel_problems(_text(fwd, bwd), layers)) == problems


def test_every_cell_that_states_a_remat_has_its_fit_cases(tmp_path):
    # the cases above are made from BENCHMARK.json as it stands, so a cell
    # that a later PR adds with a `remat` in its traffic is compiled and
    # judged here without an edit, and one without a `remat` is not
    assert NEEDS_ITS_REMAT in FIT_CELLS
    copy_data(ROOT, str(tmp_path))
    manifest = Manifest(str(tmp_path))
    assert cells_that_state_a_remat(manifest) == FIT_CELLS
    one = manifest.cells[FIT_CELLS[0]]
    manifest.cells["third-train-4k"] = dict(one, name="third-train-4k")
    manifest.cells["fourth-train-b256"] = dict(one, name="fourth-train-b256",
                                               traffic="train-b256")
    assert cells_that_state_a_remat(manifest) == FIT_CELLS + ["third-train-4k"]
