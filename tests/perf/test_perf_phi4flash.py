"""The `phi4flash` family at a size the CPU holds: the program's loss and
every leaf's gradient against `perf/reference/phi4flash.py` (the kernels in
interpret mode, under full remat), the configuration against
the published one, the counts of `perf/work_phi4flash.py` by hand at the
cell's sizes, the cell's run through the harness, the broken sides it is
held to, a program from before the family refused by name, and the cell's
lowered step, held to the text it had when it was accepted."""
import dataclasses
import hashlib
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf import program, run, work_phi4flash as work
from perf.manifest import Manifest
from perf_testdata import ROOT, copy_data

CELL = "phi4-mini-flash-train-16k"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
# 4 query heads (two differential heads) and 2 KV heads of 32 over a hidden
# size of 128, values of 64, a mamba inner width of 256 with a step rank of
# 8, a window of 32; the cell's six layers
SMALL = dict(hidden_size=128, intermediate_size=256, num_attention_heads=4,
             num_key_value_heads=2, vocab_size=256, mamba_dt_rank=8,
             sliding_window=32)
TINY = {
    "configs/phi4-mini-flash-train.json": SMALL,
    "traffic/train-16k-b1.json": dict(seq_len=128, ce_chunk=128),
    # set from six seeds on the CPU at these sizes (perf/calibrate.py and
    # perf/calibrate_phi4flash.py --root <this copy>, faults on two), as
    # PERF.md sets the cell's own on the chip, near the geometric middle of
    # the program's largest reading | the least of the fp8 control's and the
    # five faults': grad 0.035 | 0.136 (the window off), grad_median 0.0015
    # | 0.011 (the windowed layer's KV shared), change 0.021 | 0.139 (the
    # window off), change_median 0.0016 | 0.0051 (the windowed KV). The
    # step without its softplus reads inf everywhere. The losses leave no
    # room at this size: shown
    "limits/phi4-mini-flash-train-16k.json": dict(
        loss1=None, loss2=None, loss3=None, grad=0.07, grad_median=0.004,
        change=0.055, change_median=0.003),
}
tmap = jax.tree_util.tree_map


def _json(rel):
    with open(os.path.join(ROOT, "perf", rel)) as f:
        return json.load(f)


CONFIG = _json("configs/phi4-mini-flash-train.json")
TRAFFIC = _json("traffic/train-16k-b1.json")


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    to = str(tmp_path_factory.mktemp("perf_tiny_phi4flash"))
    copy_data(ROOT, to)
    for rel, changes in TINY.items():
        path = os.path.join(to, "perf", rel)
        with open(path) as f:
            data = json.load(f)
        data.update(changes)
        with open(path, "w") as f:
            json.dump(data, f)
    return to


@pytest.fixture(scope="module")
def small():
    """(config, program's configuration in float32, weights, batch)."""
    from perf.adapters import phi4flash as adapter
    from perf.reference import phi4flash as ref
    config = dict(CONFIG, **SMALL)
    cfg = dataclasses.replace(adapter.model_config(config, {"seq_len": 64}),
                              dtype=jnp.float32)
    draws = np.random.default_rng(5).integers(0, 256, (1, 65))
    batch = (jnp.asarray(draws[:, :-1]), jnp.asarray(draws[:, 1:]))
    params = jax.jit(lambda k: ref.init_params(k, config))(
        jax.random.PRNGKey(4))
    return config, cfg, params, batch


def test_loss_and_every_gradient_are_the_references(small):
    from kungfu_tpu.models.gpt import forward_features
    from kungfu_tpu.ops.chunked_ce import chunked_cross_entropy
    from perf.reference import phi4flash as ref
    config, cfg, params, batch = small

    def loss(p):
        feats = forward_features(p, batch[0], cfg, attn="flash",
                                 remat="full")
        return chunked_cross_entropy(feats, p["wte"].T, batch[1], 128).mean()

    # each side one compiled program: op by op they take twice as long
    with jax.default_matmul_precision("highest"):
        got, grads = jax.jit(jax.value_and_grad(loss))(params)
    want, want_grads, _ = jax.jit(
        lambda p: ref.loss_and_grads(p, {}, batch, config))(params)
    # float32 on both sides, sums in other orders (the scan by chunks, the
    # attention online): a few parts in a million of the loss
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(grads)[0]]
    assert len(paths) == 3 + 2 * 15 + 2 * 15 + 8 + 13
    for path, g, w in zip(paths, jax.tree_util.tree_leaves(grads),
                          jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, path               # every leaf has a gradient
        # the same float32 arithmetic to a few parts in ten thousand of the
        # leaf's largest entry: the deepest leaves' gradients pass through
        # six layers of sums taken in another order
        np.testing.assert_allclose(g, w, rtol=0, atol=5e-4 * scale,
                                   err_msg=path)


def test_the_programs_own_initialiser_makes_the_references_tree(small):
    from kungfu_tpu.models import gpt
    from perf.reference import phi4flash as ref
    shapes = lambda t: tmap(lambda x: (x.shape, x.dtype), t)
    mine = shapes(jax.eval_shape(lambda k: gpt.init_params(k, small[1]),
                                 jax.random.PRNGKey(0)))
    # but for the head, which this family ties to the embedding
    assert mine.pop("lm_head") == ((128, 256), jnp.float32)
    assert mine == shapes(jax.eval_shape(
        lambda k: ref.init_params(k, small[0]), jax.random.PRNGKey(0)))


def test_the_cell_runs_through_the_harness_and_is_correct(tiny):
    m = Manifest(tiny)
    code, result = run.drive(m, m.cell(CELL), 2 ** 31 + 99, 0.5, 0,
                             jax.devices(), PEAKS, time.perf_counter())
    assert code == 0 and result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "tokens_per_s"}
    for name in ("grad", "grad_median", "change", "change_median"):
        value, limit = result["compared"][name]
        assert value <= limit, name


@pytest.mark.parametrize("fault", ["control_fp8", "cross_reads_windowed_kv"])
def test_a_broken_side_is_not_correct(tiny, fault):
    """The reference one precision lower, and with the fault only this model
    can have that reads nearest the limits at these sizes, in the program's
    place (the chip's readings of all five: PERF.md section 2)."""
    from perf import calibrate_phi4flash as calibrate, compare, traffic_gen
    from perf.reference import train as reference
    c = Manifest(tiny).cell(CELL)
    config, traffic = c["config"], c["traffic"]
    pool = traffic_gen.make_pool(config, traffic, 8)[:3]
    args = ("phi4flash", config, traffic["optimizer"], run.key_of(8), pool)
    want = reference.follow(*args)
    if fault == "control_fp8":
        got = reference.follow(*args, quant="fp8")
    else:
        got = calibrate.follow_broken(reference, "phi4flash", fault,
                                      *args[1:])
    assert compare.decide(compare.numbers(want, want), c["limits"])[0]
    ok, compared = compare.decide(compare.numbers(got, want), c["limits"])
    assert not ok, compared


def test_a_program_from_before_the_family_refuses_the_cell_by_name(
        monkeypatch):
    from kungfu_tpu.models import gpt
    from perf.adapters import phi4flash as adapter

    @dataclasses.dataclass(frozen=True)
    class Before:
        vocab_size: int = 1
        operator: str = "attn"
    monkeypatch.setattr(gpt, "GPTConfig", Before)
    with pytest.raises(SystemExit, match="cannot run the phi4flash family"):
        adapter.model_config(CONFIG, TRAFFIC)


def test_the_configuration_is_the_published_one_cut_in_two_keys():
    """Every key of the catalog's row of the source's config.json is in the
    file with its value; the depth and the vocabulary differ, and they are
    what is listed."""
    entry = Manifest(ROOT).configs["phi4-mini-flash-train"]
    published = dict(
        embd_pdrop=0, hidden_act="silu", hidden_size=2560,
        intermediate_size=10240, layer_norm_eps=1e-05,
        max_position_embeddings=262144, mb_per_layer=2,
        model_type="phi4flash", num_attention_heads=40,
        num_hidden_layers=32, num_key_value_heads=20, resid_pdrop=0,
        sliding_window=512, tie_word_embeddings=True, mlp_bias=False,
        lm_head_bias=False, vocab_size=200064)
    differs = [k for k in published if CONFIG.get(k) != published[k]]
    assert differs == ["num_hidden_layers", "vocab_size"] == entry["reduced"]
    assert CONFIG["published"] == {k: published[k] for k in differs}
    assert sorted(CONFIG["reduced_why"]) == sorted(differs)
    assert (CONFIG["num_hidden_layers"], CONFIG["vocab_size"],
            CONFIG["first_layer"]) == (6, 200064 // 8, 14)
    assert entry["source"] == CONFIG["source"]
    assert (CONFIG["mamba_d_state"], CONFIG["mamba_d_conv"],
            CONFIG["mamba_expand"], CONFIG["mamba_dt_rank"]) == (16, 4, 2,
                                                                 160)
    for key in ("layer_roles", "mamba", "differential_attention",
                "positions", "norms", "convolution_layout", "weights"):
        assert key in CONFIG["assumed"], key
    assert "deployment" in CONFIG and "float32" in CONFIG["precision"]
    assert CONFIG["vocab_size"] % TRAFFIC["ce_chunk"] == 0
    assert TRAFFIC["remat_why"] and TRAFFIC["remat"] == "full"
    assert TRAFFIC["learning_rate_why"]
    assert (TRAFFIC["batch"], TRAFFIC["seq_len"]) == (1, 16384)


def test_the_kept_layers_are_every_kind_in_the_published_order():
    from perf.reference import phi4flash as ref
    s = ref.sizes(CONFIG)
    assert s["kinds"] == ("mamba", "swa", "mamba", "full", "gmu", "cross")
    assert (s["memory"], s["shared"]) == (2, 3)
    assert ref.attention_layers(CONFIG) == 3 and s["Dh"] == 64
    # the whole stack by the family's rule
    kinds = [ref.layer_kind(l, 32, 2) for l in range(32)]
    assert kinds[:4] == ["mamba", "swa", "mamba", "swa"]
    assert kinds[16:20] == ["mamba", "full", "gmu", "cross"]
    assert kinds.count("gmu") == kinds.count("cross") == 7
    assert s["lam0"][1] == pytest.approx(0.8 - 0.6 * np.exp(-0.3 * 15))


def test_the_state_is_697_million_weights_and_8_36_gb():
    from perf.reference import phi4flash as ref
    D, E, F = 2560, 5120, 10240
    mamba = (D * 2 * E + 4 * E + E + E * 192 + 160 * E + E + E * 16 + E
             + E * D)
    attn = D * D + 2 * D * 1280 + D * D + 4 * 64 + 128
    gmu, cross = 2 * D * E, 2 * D * D + 4 * 64 + 128
    per_layer = 3 * D * F + 4 * D
    assert (mamba, attn, gmu, cross) == (41_241_600, 19_661_184, 26_214_400,
                                         13_107_584)
    total = 2 * mamba + 2 * attn + gmu + cross + 6 * per_layer \
        + 25008 * D + 2 * D
    leaves = jax.tree_util.tree_leaves(jax.eval_shape(
        lambda: ref.init_params(jax.random.PRNGKey(0), CONFIG)))
    assert sum(x.size for x in leaves) == total == 697_073_792
    assert 12 * total == pytest.approx(8.365e9, rel=1e-3)


def test_flops_per_token_by_hand():
    D, E = 2560, 5120
    products = {"mamba": D * 2 * E + E * 192 + 160 * E + E * D,
                "attn": 2 * D * D + 2 * D * 1280, "gmu": 2 * D * E,
                "cross": 2 * D * D}
    met = (2 * products["mamba"] + 2 * products["attn"] + products["gmu"]
           + products["cross"] + 6 * 3 * D * 10240 + D * 25008)
    assert work.matmul_params_per_token(CONFIG) == met
    # visible pairs a token: two full layers and one windowed to 512
    T = 16384
    full, window = T * (T + 1) // 2, 512 * 513 // 2 + (T - 512) * 512
    per_pair = 3 * 2 * 20 * (64 + 128) * 2
    assert per_pair == 46_080
    by_hand = 6 * met + per_pair * (2 * full + window) / T
    assert work.train_flops_per_token(CONFIG, TRAFFIC) == \
        pytest.approx(by_hand, rel=1e-12)
    assert by_hand == pytest.approx(4.96e9, rel=2e-3)
    assert T * by_hand / 197e12 == pytest.approx(0.413, rel=2e-3)


def test_the_kernels_least_times_by_hand():
    T = 16384
    pairs = 2 * (T * (T + 1) // 2) + 512 * 513 // 2 + (T - 512) * 512
    flops = 46_080 * pairs
    least = work.attention_train_min_seconds(CONFIG, TRAFFIC, PEAKS)
    assert least == pytest.approx(flops / 197e12, rel=1e-12)
    # the scans: bytes over bandwidth, two layers of float32
    byts = 4 * 2 * T * ((3 * 5120 + 32) + (5 * 5120 + 64))
    assert work.scan_train_bytes(CONFIG, TRAFFIC) == byts
    assert work.scan_train_min_seconds(CONFIG, TRAFFIC, PEAKS) == \
        pytest.approx(byts / 819e9, rel=1e-12) == pytest.approx(6.56e-3,
                                                                rel=1e-2)


OWN = ["step_mfu_pct", "step_device_ms", "device_idle_pct", "forward_ms",
       "backward_ms", "recompute_ms", "ce_head_ms", "flash_fwd_ms",
       "flash_bwd_ms", "flash_roofline", "ssm_ms", "gmu_ms", "diff_mix_ms",
       "ssm_scan_ms", "ssm_scan_roofline"]


def test_the_cell_lists_its_fifteen_metrics_at_the_end():
    # appended, so every entry that was there keeps its place
    m = Manifest(ROOT)
    assert m.problems() == []
    mine = m.data["per_layer"][-len(OWN):]
    assert [x["name"] for x in mine] == [n + ".phi4flash" for n in OWN]
    assert [x["name"] for x in m.metrics("per_layer", CELL)] == [
        x["name"] for x in mine]
    layers = {x["layer"] for x in m.data["per_layer"]}
    for x in mine:
        assert x["workloads"] == [CELL] and x["moves"] == "tokens_per_s"
        assert x["layer"] in layers | {"SambaY layers (models/sambay.py)",
                                       "kernels (ops/selective_scan.py)"}
        shares = x["name"].split(".")[0].endswith(("roofline", "mfu_pct"))
        assert (x["unit"], x["better"]) == (
            ("%", "higher") if shares else
            ("%" if "pct" in x["name"] else "ms", "lower"))
        m.reader(x["name"])                   # a reader with its arguments


def test_the_metrics_files_read_the_scopes_and_the_kernels_by_name():
    from perf import trace as tracing
    from perf.readers import scope_ms, scope_roofline_in
    ms = 1_000_000
    grads = "jit(body)/grads"
    ops = [("fusion.1", 0, 2 * ms), ("ssm_scan_fwd.1", 2 * ms, 3 * ms),
           ("fusion.3", 5 * ms, 1 * ms), ("ssm_scan_bwd.1", 6 * ms, 4 * ms),
           ("fusion.5", 10 * ms, 1 * ms), ("fusion.6", 11 * ms, 2 * ms),
           ("flash_fwd.1", 13 * ms, 1 * ms)]
    labels = {
        "fusion.1": grads + "/jvp(checkpoint)/ssm_proj/dot_general "
                            "[convolution fusion]",
        "ssm_scan_fwd.1": grads + "/jvp(checkpoint)/ssm/ssm_scan_fwd/"
                                  "pallas_call [custom-call]",
        "fusion.3": grads + "/jvp(checkpoint)/ssm/mul [loop fusion]",
        "ssm_scan_bwd.1": grads + "/transpose(jvp(checkpoint))/ssm/"
                                  "ssm_scan_bwd/pallas_call [custom-call]",
        "fusion.5": grads + "/jvp(checkpoint)/diff_mix/mul [loop fusion]",
        "fusion.6": grads + "/transpose(jvp(checkpoint))/gmu/dot_general "
                            "[convolution fusion]",
        "flash_fwd.1": grads + "/jvp(checkpoint)/attn/flash_fwd/pallas_call "
                               "[custom-call]"}
    t = tracing.Trace(ops=[ops], modules=[[("jit_body(1)", 0, 14 * ms)]],
                      host_spans=[])

    def args(metric):
        kw = dict(_json(f"metrics/{metric}.json")["args"])
        return {k: kw[k] for k in ("include", "exclude") if k in kw}
    reads = lambda metric: scope_ms.scope_ns(t, labels, **args(metric)) / ms
    assert reads("ssm_ms.phi4flash") == 8             # not ssm_proj's
    assert reads("ssm_scan_ms.phi4flash") == 7
    assert reads("ssm_scan_roofline.phi4flash") == 7
    assert reads("diff_mix_ms.phi4flash") == 1
    assert reads("gmu_ms.phi4flash") == 2
    assert reads("flash_fwd_ms.phi4flash") == 1
    assert reads("backward_ms.phi4flash") == 6
    assert scope_roofline_in.read({"trace": None}, "work_phi4flash", "x",
                                  "scan_train_min_seconds", "y") is None


# sha256 of `job.lower(...).as_text()` of this cell's step at SMALL and the
# traffic's tiny cut, on the CPU, as this PR leaves it: a later PR that does
# not mean to change this program is held to it (one that does enters the
# cell's new digest in tests/data/lowered_steps.json and says so in
# CHANGES.md, as for the accepted cells)
LOWERED = "7514f31e2660ce9f0a5cb135eda788c84eed8cabf8c508590a5c06803af7b661"
LOWERED_SINCE = os.path.join(ROOT, "tests", "data", "lowered_steps.json")


def test_the_cells_lowered_step_is_the_one_accepted():
    from kungfu_tpu.comm.mesh import flat_mesh
    c = Manifest(ROOT).cell(CELL)
    config = dict(c["config"], **SMALL)
    traffic = dict(c["traffic"], **TINY["traffic/train-16k-b1.json"])
    job = program.build(config, traffic, flat_mesh(jax.devices()[:1]))
    state = jax.eval_shape(job.init_state, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((traffic["batch"], traffic["seq_len"]),
                                  jnp.int32)
    text = job.lower(state, (tokens, tokens)).as_text()
    newer = {}
    if os.path.exists(LOWERED_SINCE):
        with open(LOWERED_SINCE) as f:
            newer = json.load(f)
    got = hashlib.sha256(text.encode()).hexdigest()
    want = newer.get(CELL, LOWERED)
    assert got == want, (
        f"{CELL}'s lowered step reads {got} and is held to {want}. A PR "
        f"that means to change this program enters {{\"{CELL}\": "
        f"\"{got}\"}} in {LOWERED_SINCE} and says so in CHANGES.md")
