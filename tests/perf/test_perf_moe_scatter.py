"""The rule of the expert layer's Mosaic kernel, `moe_scatter_add`
(ops/row_scatter.py), on the step of every cell compiled at its real size
for a described v5e chip (test_perf_fit.py's topology fixture and compile
path; nothing runs):

1. a cell whose configuration routes holds the kernel at least once in each
   pass for each routed layer, and at most twice a routed layer: a third
   would be full remat running the forward loop again;
2. no while body copies a float32 array of the tokens' whole: the kernel
   adds into its carry in place, and a copy a visit would move the carry
   (268 MB at 32,768 tokens of 2048) for each block;
3. a cell that routes nothing holds no such kernel.

The routed layers are counted from the configuration file, not from the
program: `num_hidden_layers` less `num_dense_layers`, where the file names
experts."""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from perf.manifest import Manifest
from perf_testdata import ROOT
from test_perf_fit import compile_step, topo  # noqa: F401 (a fixture)

KERNEL = "moe_scatter_add"
EXPERT_KEYS = ("num_experts", "moe_num_primary_experts")


def routed_layers(config: dict) -> int:
    if not any(key in config for key in EXPERT_KEYS):
        return 0
    return config["num_hidden_layers"] - config.get("num_dense_layers", 0)


def tokens_a_pass(traffic: dict) -> int:
    return traffic["batch"] * traffic["seq_len"] // traffic.get(
        "accum_steps", 1)


CELLS = Manifest(ROOT).cells
ROUTED = sorted(c for c in CELLS
                if routed_layers(Manifest(ROOT).cell(c)["config"]))
UNROUTED = sorted(set(CELLS) - set(ROUTED))


def scatter_problems(text: str, layers: int) -> list:
    """What rule 1 (and 3, at `layers` 0) finds wrong in a step's text: the
    kernel's Mosaic calls told by the `op_name` on their line, a backward
    one by jax's mark `transpose(`."""
    calls = [m.group(1) for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and (m := re.search(r'op_name="([^"]*)"', line))
             and KERNEL in m.group(1)]
    backward = sum("transpose(" in call for call in calls)
    out = [f"{n} {pas} {KERNEL} calls for {layers} routed layers"
           for pas, n in (("forward", len(calls) - backward),
                          ("backward", backward)) if n < layers]
    if len(calls) > 2 * layers:
        out.append(f"{len(calls)} {KERNEL} calls for {layers} routed layers:"
                   f" over {2 * layers}, so a pass runs its loop again")
    return out


def carry_copies(text: str, tokens: int, width: int) -> list:
    """Rule 2: the `copy` instructions, inside the computations that are a
    while's body, of a float32 array of `tokens` rows that holds at least
    `tokens * width` elements."""
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    out, inside = [], False
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            name = line.split()[1] if line.startswith("ENTRY") else \
                line.split()[0]
            inside = name.lstrip("%") in bodies
        m = re.search(r"= f32\[([\d,]+)\]\S* copy\(", line)
        if inside and m:
            dims = [int(d) for d in m.group(1).split(",")]
            if dims[0] == tokens and math.prod(dims) >= tokens * width:
                out.append(line.strip().split(" = ")[0])
    return out


@pytest.fixture(scope="module", params=ROUTED)
def routed(request, topo):
    """(the cell, its compiled step's text): one compile a cell."""
    cell = Manifest(ROOT).cell(request.param)
    return cell, compile_step(topo, cell)[0].as_text()


def test_the_kernel_is_in_each_pass_once_a_routed_layer(routed):
    cell, text = routed
    assert not scatter_problems(text, routed_layers(cell["config"]))


def test_no_loop_copies_a_carry_of_the_tokens(routed):
    cell, text = routed
    assert not carry_copies(text, tokens_a_pass(cell["traffic"]),
                            cell["config"]["hidden_size"])


def _batch(traffic: dict, config: dict, sharding) -> tuple:
    """The shapes of a batch as perf/traffic_gen.py draws it."""
    b = traffic["batch"]
    if traffic["input"] == "tokens":
        tokens = jax.ShapeDtypeStruct((b, traffic["seq_len"]), jnp.int32,
                                      sharding=sharding)
        return tokens, tokens
    size = config["image_size"]
    return (jax.ShapeDtypeStruct((b, size, size, 3), jnp.uint8,
                                 sharding=sharding),
            jax.ShapeDtypeStruct((b,), jnp.int32, sharding=sharding))


@pytest.mark.parametrize("name", UNROUTED)
def test_a_cell_that_routes_nothing_holds_no_such_kernel(name, topo,
                                                         monkeypatch):
    # lowered at its real size for the chip: a Mosaic call is in the module
    # from the start, so the lowering holds whatever the compile would
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
    text = job.lower(state, _batch(cell["traffic"], cell["config"],
                                   sharding)).as_text()
    assert routed_layers(cell["config"]) == 0
    assert "tpu_custom_call" in text or cell["config"]["family"] == "resnet"
    assert KERNEL not in text


def _text(fwd: int, bwd: int, **others: int) -> str:
    """A compiled step's text as far as rule 1 reads it."""
    call = ('%c = f32[1] custom-call(), custom_call_target="tpu_custom_call",'
            ' metadata={{op_name="jit(body)/grads/{}/moe/while/body/'
            'moe_route/{}/pallas_call"}}\n')
    return (call.format("jvp(ffn)", KERNEL) * fwd
            + call.format("transpose(jvp(ffn))", KERNEL) * bwd
            + "".join(call.format("jvp(attn)", name) * n
                      for name, n in others.items()))


@pytest.mark.parametrize("fwd, bwd, others, layers, problems", [
    # once a pass a layer; a layer's forward or backward without it
    (4, 4, {}, 4, 0), (3, 4, {}, 4, 1), (4, 3, {}, 4, 1), (0, 0, {}, 4, 2),
    # the forward loop run again under remat: three a layer
    (8, 4, {}, 4, 1),
    # other kernels are not this rule's
    (4, 4, {"flash_fwd": 12}, 4, 0),
    # a cell that routes nothing: none is right, one is a problem
    (0, 0, {"flash_fwd": 2}, 0, 0), (1, 0, {}, 0, 1), (0, 1, {}, 0, 1),
])
def test_the_count_of_the_kernel_says_yes_and_no(fwd, bwd, others, layers,
                                                 problems):
    assert len(scatter_problems(_text(fwd, bwd, **others), layers)) == \
        problems


_LOOP = """%body.7 (p: (s32[], f32[8192,24,128])) -> (s32[], f32[8192,24,128]) {{
  %x = f32[8192,24,128]{{2,1,0:T(8,128)}} get-tuple-element(%p), index=1
  %{name} = {shape}{{1,0:T(8,128)}} copy(%x)
}}

ENTRY %main.9 (a: f32[8192,2560]) -> f32[8192,2560] {{
  %entry_copy = f32[8192,2560]{{1,0:T(8,128)}} copy(%a)
  %w = (s32[], f32[8192,24,128]) while(%t), condition=%cond.8, body=%body.7
}}
"""


@pytest.mark.parametrize("shape, copies", [
    # the carry in either layout, copied in the loop
    ("f32[8192,24,128]", 1), ("f32[8192,2560]", 1),
    # a block's rows, another dtype, fewer elements than the tokens' whole
    ("f32[512,2560]", 0), ("bf16[8192,2560]", 0), ("f32[8192,128]", 0)])
def test_a_copy_of_the_carry_in_a_loop_is_found(shape, copies):
    text = _LOOP.format(name="copy.3", shape=shape)
    found = carry_copies(text, 8192, 2560)
    assert len(found) == copies
    assert "%entry_copy" not in found          # outside any loop
