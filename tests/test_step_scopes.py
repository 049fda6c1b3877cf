"""The names a device trace is read by (docs/monitoring.md, "Scope names"):
the lowered step of each builder carries every class of `op_name` the
benchmark's per-layer metrics look for, the program is still called
`jit_body`, the Mosaic kernels have names of their own, and a scope changes
no output. All on the CPU: a scope is metadata of the program, the same
whatever compiles it."""
import contextlib
import dataclasses
import functools
import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import kungfu_tpu.optimizers as kfopt
from kungfu_tpu.comm.mesh import flat_mesh
from kungfu_tpu.models import looped
from kungfu_tpu.models.gpt import GPTConfig, forward_features, init_params
from kungfu_tpu.models.resnet import ResNet
from kungfu_tpu.ops.chunked_ce import chunked_cross_entropy
from kungfu_tpu.training import (build_train_step,
                                 build_train_step_with_state, init_opt_state,
                                 replicate)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GPT = GPTConfig(vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=128,
                max_seq=128, n_kv_heads=2, rope=True, mlp="swiglu")
GPT_METRICS = ["forward_ms.gpt", "backward_ms.gpt", "recompute_ms.gpt",
               "optimizer_ms.gpt", "accumulate_ms.gpt", "ce_head_ms.gpt",
               "ce_head_other_ms.gpt"]
FLASH_METRICS = ["flash_fwd_ms.gpt", "flash_bwd_ms.gpt",
                 "flash_fwd_ms.ouro", "flash_bwd_ms.ouro",
                 "flash_fwd_ms.smallthinker", "flash_bwd_ms.smallthinker",
                 "flash_fwd_ms.lfm2", "flash_bwd_ms.lfm2",
                 "flash_fwd_ms.phi4flash", "flash_bwd_ms.phi4flash"]
# the looped decoder: the same layers, four rounds under one set of weights
OURO = dataclasses.replace(GPT, n_kv_heads=4, norm_eps=1e-6, rope_theta=1e6,
                           out_norms=True, n_rounds=4)
OURO_METRICS = ["forward_ms.ouro", "backward_ms.ouro", "recompute_ms.ouro",
                "optimizer_ms.ouro", "accumulate_ms.ouro", "ce_head_ms.ouro",
                "ce_head_other_ms.ouro", "exit_ms.ouro",
                "loop_other_ms.ouro"]
RESNET_METRICS = ["forward_ms.resnet", "backward_ms.resnet"]
# the sparse decoder: a head size of its own, layers of two kinds, a routed
# feed-forward without dropped tokens over the experts held here
SPARSE = GPTConfig(vocab_size=256, d_model=40, n_heads=6, d_head=8,
                   n_kv_heads=2, n_layers=2, d_ff=0, max_seq=128,
                   rope=(False, True), window=(None, 64), mlp="reglu",
                   n_experts=8, experts_per_token=3, d_expert=16,
                   experts_held=(2, 4), norm_eps=1e-6, rope_theta=1.5e6)
SPARSE_METRICS = ["forward_ms.smallthinker", "backward_ms.smallthinker",
                  "recompute_ms.smallthinker", "optimizer_ms.smallthinker",
                  "accumulate_ms.smallthinker", "ce_head_ms.smallthinker",
                  "moe_ms.smallthinker", "moe_route_ms.smallthinker",
                  "gmm_ms.smallthinker"]
# the hybrid decoder: a dense conv layer, then attention with qk-norm and a
# conv layer, routed by sigmoid scores with a bias that the step moves
HYBRID = GPTConfig(vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2,
                   n_layers=3, d_ff=96, max_seq=128, rope=True, mlp="swiglu",
                   n_experts=8, experts_per_token=2, d_expert=32,
                   experts_held=(2, 4), routed=(False, True, True),
                   router="sigmoid", router_reads="ln2",
                   operator=("conv", "attn", "conv"), qk_norm=True)
HYBRID_METRICS = ["forward_ms.lfm2", "backward_ms.lfm2", "recompute_ms.lfm2",
                  "optimizer_ms.lfm2", "ce_head_ms.lfm2", "moe_ms.lfm2",
                  "moe_route_ms.lfm2", "gmm_ms.lfm2", "conv_ms.lfm2"]

# the decoder-hybrid-decoder: mamba, windowed differential attention, the
# mamba that makes the memory, the full attention whose KV is shared, a
# gated memory unit and a cross-attention layer
SAMBAY = GPTConfig(vocab_size=256, d_model=64, n_heads=8, n_kv_heads=4,
                   d_head=16, n_layers=6, d_ff=96, max_seq=128,
                   rope=(False,) * 6, mlp="swiglu",
                   window=(None, 32, None, None, None, None),
                   operator=("mamba", "diff", "mamba", "diff", "gmu",
                             "cross"), norm="layer")
SAMBAY_METRICS = ["forward_ms.phi4flash", "backward_ms.phi4flash",
                  "recompute_ms.phi4flash", "ce_head_ms.phi4flash",
                  "ssm_ms.phi4flash", "gmu_ms.phi4flash",
                  "diff_mix_ms.phi4flash"]
# read by a kernel's name, which only the chip's step holds (the CPU takes
# the scan's jnp path inside shard_map): checked on the lowered kernels
SCAN_METRICS = ["ssm_scan_ms.phi4flash"]


def scope_metrics() -> dict:
    """{metric: its reader's arguments} of the benchmark's `scope_ms`
    metrics, from the files the harness reads."""
    out = {}
    for path in glob.glob(os.path.join(ROOT, "perf", "metrics", "*.json")):
        with open(path) as f:
            spec = json.load(f)
        if spec["reader"] == "scope_ms":
            out[os.path.basename(path)[:-len(".json")]] = spec["args"]
    return out


def found(args: dict, op_names) -> list:
    """The op_names a metric's patterns keep. A label in the trace is the
    op_name with the profiler's category behind it; the CPU has none."""
    want = re.compile(args["include"])
    skip = re.compile(args["exclude"]) if args.get("exclude") else None
    return [n for n in op_names
            if want.search(n + " []") and not (skip and skip.search(n))]


@pytest.fixture(scope="module")
def mesh():
    return flat_mesh(jax.devices()[:1])


def make_gpt_step(mesh):
    def loss_fn(p, batch):
        tokens, targets = batch
        feats = forward_features(p, tokens, GPT, attn="flash", remat="full")
        return chunked_cross_entropy(feats, p["lm_head"].astype(GPT.dtype),
                                     targets, 128).mean()

    opt = kfopt.synchronous_sgd(optax.adamw(1e-3))
    step = build_train_step(loss_fn, opt, mesh, donate=False, accum_steps=4,
                            compute_dtype=jnp.bfloat16)
    params = replicate(init_params(jax.random.PRNGKey(0), GPT), mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 128), 0, 256)
    return step, (params, init_opt_state(opt, params, mesh),
                  (tokens, jnp.roll(tokens, -1, axis=1)))


def make_ouro_step(mesh):
    def loss_fn(p, batch):
        tokens, targets = batch
        return looped.loss_fn(p, tokens, targets, OURO, beta=0.1,
                              ce_chunk=128, attn="flash", remat="full")

    opt = kfopt.synchronous_sgd(optax.adamw(1e-3))
    step = build_train_step(loss_fn, opt, mesh, donate=False, accum_steps=2,
                            compute_dtype=jnp.bfloat16)
    params = replicate(looped.init_params(jax.random.PRNGKey(0), OURO), mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 256)
    return step, (params, init_opt_state(opt, params, mesh),
                  (tokens, jnp.roll(tokens, -1, axis=1)))


def make_sparse_step(mesh):
    def loss_fn(p, batch):
        tokens, targets = batch
        feats = forward_features(p, tokens, SPARSE, attn="flash",
                                 remat="full")
        return chunked_cross_entropy(
            feats, p["lm_head"].astype(SPARSE.dtype), targets, 128).mean()

    opt = kfopt.synchronous_sgd(optax.adamw(1e-6))
    step = build_train_step(loss_fn, opt, mesh, donate=False, accum_steps=2,
                            compute_dtype=jnp.bfloat16)
    params = replicate(init_params(jax.random.PRNGKey(0), SPARSE), mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 256)
    return step, (params, init_opt_state(opt, params, mesh),
                  (tokens, jnp.roll(tokens, -1, axis=1)))


def make_hybrid_step(mesh):
    from kungfu_tpu.parallel.moe import update_expert_bias

    def loss_fn(p, mstate, batch):
        tokens, targets = batch
        feats, loads = forward_features(p, tokens, HYBRID, attn="flash",
                                        remat="full", router_bias=mstate)
        loss = chunked_cross_entropy(
            feats, p["wte"].astype(HYBRID.dtype).T, targets, 128).mean()
        return loss, [update_expert_bias(b, n, 1e-3)
                      for b, n in zip(mstate, loads)]

    opt = kfopt.synchronous_sgd(optax.adamw(1e-6))
    step = build_train_step_with_state(loss_fn, opt, mesh, donate=False,
                                       sync_model_state=False,
                                       compute_dtype=jnp.bfloat16)
    params = init_params(jax.random.PRNGKey(0), HYBRID)
    del params["lm_head"]
    params = replicate(params, mesh)
    bias = replicate([jnp.zeros(8)] * 2, mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 256)
    return step, (params, init_opt_state(opt, params, mesh), bias,
                  (tokens, jnp.roll(tokens, -1, axis=1)))


def make_resnet_step(mesh):
    model = ResNet(stage_sizes=[1, 1], num_classes=10, num_filters=8)

    def loss_fn(p, mstate, batch):
        images, labels = batch
        logits, updated = model.apply(
            {"params": p, "batch_stats": mstate}, images, train=True,
            mutable=["batch_stats"])
        return (optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean(), updated["batch_stats"])

    images = jax.random.normal(jax.random.PRNGKey(2), (4, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), images, train=True)
    opt = kfopt.synchronous_sgd(optax.sgd(0.1, momentum=0.9))
    step = build_train_step_with_state(loss_fn, opt, mesh, donate=False)
    params = replicate(variables["params"], mesh)
    return step, (params, init_opt_state(opt, params, mesh),
                  replicate(variables["batch_stats"], mesh),
                  (images, jnp.arange(4, dtype=jnp.int32)))


def make_sambay_step(mesh):
    def loss_fn(p, batch):
        tokens, targets = batch
        feats = forward_features(p, tokens, SAMBAY, attn="flash",
                                 remat="full")
        return chunked_cross_entropy(
            feats, p["wte"].astype(SAMBAY.dtype).T, targets, 128).mean()

    opt = kfopt.synchronous_sgd(optax.adamw(1e-5))
    step = build_train_step(loss_fn, opt, mesh, donate=False,
                            compute_dtype=jnp.bfloat16)
    # made in one program: op by op the mixers' many shapes take twice as
    # long
    params = jax.jit(functools.partial(init_params, cfg=SAMBAY))(
        jax.random.PRNGKey(0))
    del params["lm_head"]
    params = replicate(params, mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 128), 0, 256)
    return step, (params, init_opt_state(opt, params, mesh),
                  (tokens, jnp.roll(tokens, -1, axis=1)))


MAKERS = {"gpt": make_gpt_step, "resnet": make_resnet_step,
          "ouro": make_ouro_step, "smallthinker": make_sparse_step,
          "lfm2": make_hybrid_step, "phi4flash": make_sambay_step}


@pytest.fixture(scope="module")
def steps(mesh):
    return {which: make(mesh) for which, make in MAKERS.items()}


@pytest.fixture(scope="module")
def names(steps):
    """{family: the op_names of its compiled step}."""
    return {which: set(re.findall(
        r'op_name="([^"]*)"', step.lower(*args).compile().as_text()))
        for which, (step, args) in steps.items()}


# the flash kernels run only on the chip inside shard_map (the CPU takes the
# jnp path there): their names are checked on the lowered kernels below
@pytest.mark.parametrize("metric", GPT_METRICS + RESNET_METRICS
                         + OURO_METRICS + SPARSE_METRICS + HYBRID_METRICS
                         + SAMBAY_METRICS)
def test_the_step_has_what_each_metric_reads(names, metric):
    family = metric.rsplit(".", 1)[1]
    assert found(scope_metrics()[metric], names[family])


def test_every_scope_metric_of_the_manifest_is_covered_here():
    assert set(scope_metrics()) == set(GPT_METRICS + FLASH_METRICS
                                       + RESNET_METRICS + OURO_METRICS
                                       + SPARSE_METRICS + HYBRID_METRICS
                                       + SAMBAY_METRICS + SCAN_METRICS)


@pytest.mark.parametrize("which", ["gpt", "resnet", "ouro", "smallthinker",
                                   "lfm2", "phi4flash"])
def test_the_builders_scopes(names, which):
    has = lambda rx: any(re.search(rx, n) for n in names[which])
    for scope in ("grads", "optimizer", "sync"):
        assert has(rf"^jit\(body\)/{scope}/"), scope


def test_gpt_scopes_by_pass(names):
    has = lambda rx: any(re.search(rx, n) for n in names["gpt"])
    assert has(r"/grads/while/body/.*accumulate/")
    for scope in ("embed", "attn", "ffn", "final_norm"):
        assert has(rf"/grads/.*jvp\({scope}\)"), scope
    # full remat: the layer again for the backward, under its own mark
    assert has(r"transpose\(.*rematted_computation/attn/")
    assert has(r"transpose\(.*rematted_computation/ffn/")
    # a custom_vjp's backward is traced apart: ce_head is in both passes
    assert has(r"/jvp\(ce_head\)/")
    assert has(r"/transpose\(jvp\(ce_head\)\)/")
    # the forward metric leaves the other two passes out
    forward = found(scope_metrics()["forward_ms.gpt"], names["gpt"])
    assert not [n for n in forward
                if "transpose(" in n or "rematted_computation" in n]


def test_the_conv_scope_holds_the_gates_and_taps_not_the_projections(names):
    has = lambda rx: any(re.search(rx, n) for n in names["lfm2"])
    # both passes and full remat's second forward: conv_ms reads them all
    assert has(r"/jvp\(conv\)/") and has(r"transpose\(.*/conv/")
    assert has(r"rematted_computation/conv/")
    # W_in and W_out stand under conv_proj, which conv_ms does not read
    proj = [n for n in names["lfm2"] if "conv_proj" in n]
    assert any(n.endswith("dot_general") for n in proj)
    assert not found(scope_metrics()["conv_ms.lfm2"], proj)
    assert not [n for n in found(scope_metrics()["conv_ms.lfm2"],
                                 names["lfm2"]) if "dot_general" in n]
    # the router reads ln2's output: it stands under ffn, not attn
    assert has(r"jvp\(ffn\)/moe/moe_route")
    assert not has(r"attn/.*moe_route")


def test_the_sambay_scopes_hold_their_parts_and_no_projection(names):
    has = lambda rx: any(re.search(rx, n) for n in names["phi4flash"])
    # the mixers' scopes in the forward, the backward and full remat's
    # second forward
    for scope in ("ssm", "ssm_proj", "gmu", "diff_mix", "attn"):
        assert has(rf"/jvp\({scope}\)/"), scope
        assert has(rf"transpose\(.*/{scope}/"), scope
    assert has(r"rematted_computation/ssm/")
    # W_in and W_out stand under ssm_proj, which ssm_ms does not read
    proj = [n for n in names["phi4flash"] if "ssm_proj" in n]
    assert any(n.endswith("dot_general") for n in proj)
    assert not found(scope_metrics()["ssm_ms.phi4flash"], proj)


def test_the_scan_kernels_carry_their_names():
    from kungfu_tpu.ops.selective_scan import _scan
    u = jax.ShapeDtypeStruct((1, 16, 8), jnp.float32)
    A = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    B = jax.ShapeDtypeStruct((1, 16, 4), jnp.float32)
    grad = jax.jit(jax.grad(lambda u, d, A, B, C: _scan(
        u, d, A, B, C, jnp.float32).sum(), argnums=(0, 1, 2, 3, 4)))
    jaxpr = str(jax.make_jaxpr(grad)(u, u, A, B, B))
    assert "name=ssm_scan_fwd" in jaxpr and "name=ssm_scan_bwd" in jaxpr
    label = ("jit(body)/grads/jvp(checkpoint)/ssm/{}/pallas_call")
    labels = [label.format(k) for k in ("ssm_scan_fwd", "ssm_scan_bwd")]
    assert found(scope_metrics()["ssm_scan_ms.phi4flash"], labels) == labels


def test_ouro_scopes_stand_inside_the_round_loop(names):
    """`ut_loop` stands around the scan of rounds, so jax's marks of the
    pass stand on it and the layers' scopes inside the loop's body: the
    pass metrics read the rounds as they read a plain stack, and the
    loop's own operations carry the program's name, not only `while`."""
    has = lambda rx: any(re.search(rx, n) for n in names["ouro"])
    for scope in ("attn", "ffn", "final_norm"):
        assert has(rf"jvp\(ut_loop\)/while/body/.*/{scope}/"), scope
    assert has(r"transpose\(jvp\(ut_loop\)\)/while/body/.*"
               r"rematted_computation/ffn/")
    assert has(r"jvp\(exit_gate\)") and has(r"jvp\(exit_mix\)")
    # the heads' map is a loop too, under the head's name
    assert has(r"jvp\(ce_head\)/while/body/.*/ce_head/")
    assert has(r"transpose\(jvp\(ce_head\)\)/while/body/dynamic_")
    other = found(scope_metrics()["loop_other_ms.ouro"], names["ouro"])
    assert [n for n in other if n.endswith("/while/body/dynamic_slice")]
    assert [n for n in other if n.endswith("closed_call/add_any")]
    assert not [n for n in other
                if re.search("attn|ffn|final_norm|ce_head", n)]
    # no loop of the model's is left without a name of the program's
    assert not has(r"jvp\(\)\)?/while")


def test_resnet_passes_are_told_by_jaxs_own_marks(names):
    has = lambda rx: any(re.search(rx, n) for n in names["resnet"])
    assert has(r"/grads/jvp\(ResNet\)/BottleneckBlock_0/Conv_\d+/")
    assert has(r"/grads/transpose\(jvp\(ResNet\)\)/.*/BatchNorm_\d+/")


@pytest.mark.parametrize("which", ["gpt", "resnet", "ouro"])
def test_the_program_is_still_jit_body(steps, which):
    # the accepted step_device_ms.* and flash_roofline find the step's
    # executions in the trace by this name
    step, args = steps[which]
    assert re.search(r"^HloModule jit_body\b",
                     step.lower(*args).compile().as_text(), re.M)


def test_the_builder_with_state_still_returns_the_jitted_object(steps):
    assert isinstance(steps["resnet"][0], type(jax.jit(lambda: 0)))


@pytest.mark.parametrize("which", ["gpt", "resnet"])
def test_a_scope_changes_no_output(mesh, steps, which, monkeypatch):
    step, args = steps[which]
    with_scopes = step(*args)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare, _ = MAKERS[which](mesh)
    bare_names = set(re.findall(r'op_name="([^"]*)"',
                                bare.lower(*args).compile().as_text()))
    assert not [n for n in bare_names
                if re.search(r"/(grads|optimizer|sync)/", n)]
    without = bare(*args)
    for a, b in zip(jax.tree_util.tree_leaves(with_scopes),
                    jax.tree_util.tree_leaves(without)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


FLASH_KERNELS = ("flash_fwd", "flash_bwd_delta", "flash_bwd_dq",
                 "flash_bwd_dkv")


def lowered_flash_grad(T, heads, kv_heads):
    """(the lowered module, the jaxpr) of the causal flash gradient at
    [1, T, heads, 128] in bf16: lowered, never run."""
    from kungfu_tpu.ops.flash_attention import flash_attention
    q = jax.ShapeDtypeStruct((1, T, heads, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, T, kv_heads, 128), jnp.bfloat16)
    grad = jax.jit(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True,
            kv_groups=heads // kv_heads).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))
    return (grad.lower(q, k, k).as_text(debug_info=True),
            str(jax.make_jaxpr(grad)(q, k, k)))


@pytest.fixture(scope="module")
def flash_grad():
    # a sequence over the fused backward's budget (8192 at heads of 128):
    # the dq kernel runs beside the dk/dv kernel, all four names are there
    return lowered_flash_grad(9216, 2, 1)


@pytest.mark.parametrize("kernel", FLASH_KERNELS)
def test_the_flash_kernels_carry_their_names(flash_grad, kernel):
    lowered, jaxpr = flash_grad
    assert kernel in lowered
    assert f"name={kernel}" in jaxpr


def test_at_the_cells_shape_the_backward_is_delta_and_dkv():
    # 4096 x 128, both token cells: one kernel makes dq, dk and dv under
    # the name the benchmark's flash_bwd_ms.* already read
    lowered, jaxpr = lowered_flash_grad(4096, 4, 1)
    # (`flash_out` and `flash_lse` beside them name values, not kernels)
    assert set(re.findall(r"name=(flash_(?:fwd|bwd)\w*)", jaxpr)) == {
        "flash_fwd", "flash_bwd_delta", "flash_bwd_dkv"}
    assert "flash_bwd_dkv" in lowered and "flash_bwd_dq" not in lowered


@pytest.mark.parametrize("metric", FLASH_METRICS)
def test_the_flash_metrics_find_the_kernels_by_name(metric):
    # what the trace's label of a kernel looks like on the chip
    # (inside the looped decoder's scan the mark stands on the loop)
    under = ("jvp(attn)" if metric.endswith(".gpt")
             else "jvp(ut_loop)/while/body/closed_call/attn")
    labels = [f"jit(body)/grads/while/body/closed_call/{under}/{k}/"
              "pallas_call" for k in FLASH_KERNELS]
    kept = found(scope_metrics()[metric], labels)
    assert kept == ([labels[0]] if metric.startswith("flash_fwd_ms")
                    else labels[1:])


def test_the_paged_kernel_carries_its_name():
    from kungfu_tpu.ops.paged_attention import paged_attention
    args = (jnp.zeros((2, 4, 32)), jnp.zeros((8, 16, 2, 32)),
            jnp.zeros((8, 16, 2, 32)), jnp.zeros((2, 2), jnp.int32),
            jnp.zeros((2,), jnp.int32))
    assert "name=paged_attention" in str(
        jax.make_jaxpr(paged_attention)(*args))
    assert "paged_attention" in jax.jit(paged_attention).lower(
        *args).as_text(debug_info=True)
