"""The rule of the selective scan's Mosaic kernels (ops/selective_scan.py),
on the step of every cell lowered at its real size for a described v5e
chip (test_perf_fit.py's topology fixture; nothing compiles or runs: a
Mosaic call is in the module from the start, and what full remat makes
again is settled before lowering):

1. a cell whose configuration has mamba layers holds the forward kernel
   `ssm_scan_fwd` once a mamba layer and the backward kernel `ssm_scan_bwd`
   once a mamba layer: no more, since full remat keeps what the forward
   kernel made and a second forward call would be the layer's scan run
   again;
2. a cell with no mamba layer holds neither.

The mamba layers are counted by the family's reference, not the program:
the kinds `perf/reference/<family>.py` `sizes` gives, where it gives them."""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from perf.manifest import Manifest
from perf_testdata import ROOT
from test_perf_fit import topo  # noqa: F401 (a fixture)
from test_perf_moe_scatter import _batch

KERNELS = ("ssm_scan_fwd", "ssm_scan_bwd")


def mamba_layers(config: dict) -> int:
    from perf.reference import train
    sizes = getattr(train.family(config["family"]), "sizes", None)
    return list(sizes(config).get("kinds", ()) if sizes else ()).count(
        "mamba")


CELLS = Manifest(ROOT).cells
SCANS = sorted(c for c in CELLS if mamba_layers(Manifest(ROOT).cell(c)[
    "config"]))
OTHERS = sorted(set(CELLS) - set(SCANS))


def scan_problems(text: str, layers: int) -> list:
    """What rules 1 and 2 (at `layers` 0) find wrong in a step's text: the
    kernels' Mosaic calls, each told by the kernel's name on its line."""
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    return [f"{n} {name} calls for {layers} mamba layers"
            for name in KERNELS
            if (n := sum(name in call for call in calls)) != layers]


def lowered(topo, name: str, monkeypatch) -> tuple:
    """(the cell, its step's lowered text) at its real size, for the chip."""
    from kungfu_tpu.comm.mesh import PEER_AXIS
    from kungfu_tpu.ops import flash_attention
    from perf import program
    cell = Manifest(ROOT).cell(name)
    mesh = Mesh(np.array(topo.devices[:1]), (PEER_AXIS,))
    sharding = NamedSharding(mesh, P(mesh.axis_names))
    monkeypatch.setattr(flash_attention, "_auto_interpret", lambda: False)
    job = program.build(cell["config"], cell["traffic"], mesh)
    state = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        jax.eval_shape(job.init_state, jax.random.PRNGKey(0)))
    return cell, job.lower(state, _batch(cell["traffic"], cell["config"],
                                         sharding)).as_text()


def test_the_cells_with_mamba_layers_are_the_new_one():
    assert SCANS == ["phi4-mini-flash-train-16k"]
    assert mamba_layers(Manifest(ROOT).cell(SCANS[0])["config"]) == 2


@pytest.mark.parametrize("name", SCANS)
def test_the_kernels_are_in_the_step_once_a_mamba_layer_a_pass(name, topo,
                                                               monkeypatch):
    cell, text = lowered(topo, name, monkeypatch)
    assert not scan_problems(text, mamba_layers(cell["config"]))


@pytest.mark.parametrize("name", OTHERS)
def test_a_cell_without_mamba_layers_holds_no_scan_kernel(name, topo,
                                                          monkeypatch):
    cell, text = lowered(topo, name, monkeypatch)
    assert mamba_layers(cell["config"]) == 0
    assert not scan_problems(text, 0)
    assert "tpu_custom_call" in text or cell["config"]["family"] == "resnet"
    assert not any(kernel in text for kernel in KERNELS)


def _text(fwd: int, bwd: int, **others: int) -> str:
    call = ('%c = f32[1] custom-call(), custom_call_target="tpu_custom_call",'
            ' metadata={{op_name="jit(body)/grads/{}/ssm/{}/pallas_call"}}\n')
    return (call.format("jvp(checkpoint)", "ssm_scan_fwd") * fwd
            + call.format("transpose(jvp(checkpoint))", "ssm_scan_bwd") * bwd
            + "".join(call.format("jvp(attn)", name) * n
                      for name, n in others.items()))


@pytest.mark.parametrize("fwd, bwd, others, layers, problems", [
    # once a pass a layer, beside other kernels
    (2, 2, {}, 2, 0), (2, 2, {"flash_fwd": 3}, 2, 0),
    # a layer without its forward or its backward; the scan run again
    (1, 2, {}, 2, 1), (2, 1, {}, 2, 1), (4, 2, {}, 2, 1), (0, 0, {}, 2, 2),
    # a cell with no mamba layer: none is right, one is a problem
    (0, 0, {"flash_fwd": 2}, 0, 0), (1, 0, {}, 0, 1), (0, 1, {}, 0, 1),
])
def test_the_count_of_the_kernels_says_yes_and_no(fwd, bwd, others, layers,
                                                  problems):
    assert len(scan_problems(_text(fwd, bwd, **others), layers)) == problems
