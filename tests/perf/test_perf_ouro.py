"""`correct` for the looped decoder's cell at a size the CPU holds: the
program agrees with `perf/reference/ouro.py`; the reference one precision
lower does not; and the two broken timed paths that only this model can
have (one round too few, every exit weighted alike) come out as not
correct."""
import importlib
import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

from perf import compare, run, traffic_gen
from perf.manifest import Manifest
from perf.reference import train as reference
from perf_testdata import ROOT, copy_data

CELL = "ouro2.6b-train-4k"
PEAKS = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11}
# set from six seeds on the CPU at these sizes (perf/calibrate.py --root
# <this copy>), as tests/perf/perf_testdata.py sets the other cells': above
# the program's largest reading, below the least of the fp8 control's and
# the half batch's. Largest program | least of those two: grad 0.012 | 0.46,
# grad_median 0.0024 | 0.15, change 0.0033 | 0.13, change_median 0.00073 |
# 0.0073. The two faults of the loop are told by `grad`: one round too few
# reads 0.30 (grad_median 0.010 and change 0.0157 stay within), every exit
# weighted alike reads 1 (the gate gets no gradient). At the cell's own size
# on the chip both were read once too (PERF.md section 2): one round too
# few reads grad 0.29, grad_median 0.13 and loss1 1.75e-3, all over the
# cell's limits.
TINY = {
    "configs/ouro2.6b-train.json": dict(
        hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=4, head_dim=16, vocab_size=256,
        num_hidden_layers=2),
    "traffic/train-4k-b2.json": dict(seq_len=128, ce_chunk=128),
    "limits/ouro2.6b-train-4k.json": dict(
        loss1=None, loss2=None, loss3=None, grad=0.06, grad_median=0.012,
        change=0.016, change_median=0.003),
}
tmap = jax.tree_util.tree_map


@pytest.fixture(scope="module")
def tiny_ouro(tmp_path_factory):
    to = str(tmp_path_factory.mktemp("perf_tiny_ouro"))
    copy_data(ROOT, to)
    for rel, changes in TINY.items():
        path = os.path.join(to, "perf", rel)
        with open(path) as f:
            data = json.load(f)
        data.update(changes)
        with open(path, "w") as f:
            json.dump(data, f)
    return to


def _drive(root, seed=7, seconds=0.5):
    m = Manifest(root)
    code, result = run.drive(m, m.cell(CELL), seed, seconds, 0,
                             jax.devices(), PEAKS, time.perf_counter())
    assert code == 0
    return result


def test_the_program_agrees_with_its_reference(tiny_ouro):
    result = _drive(tiny_ouro, seed=2 ** 31 + 12345)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    for name, (value, limit) in result["compared"].items():
        assert limit is None or value <= limit, name
    assert set(result["metrics"]) == {"setup_s", "tokens_per_s"}


def test_the_control_one_precision_lower_is_not_correct(tiny_ouro):
    c = Manifest(tiny_ouro).cell(CELL)
    config, traffic = c["config"], c["traffic"]
    pool = traffic_gen.make_pool(config, traffic, 3)[:traffic[
        "reference_steps"]]
    args = ("ouro", config, traffic["optimizer"], run.key_of(3), pool)
    want = reference.follow(*args)
    ok, compared = compare.decide(
        compare.numbers(reference.follow(*args, quant="fp8"), want),
        c["limits"])
    assert not ok, compared
    assert compare.decide(compare.numbers(want, want), c["limits"])[0]


def _one_round_too_few(monkeypatch, adapter):
    build = adapter.build

    def broken(config, traffic, mesh):
        return build(dict(config,
                          total_ut_steps=config["total_ut_steps"] - 1),
                     traffic, mesh)
    monkeypatch.setattr(adapter, "build", broken)


def _every_exit_weighted_alike(monkeypatch, adapter):
    """The exit gate and the entropy term left out: p_r = 1 / R."""
    from kungfu_tpu.models import looped
    monkeypatch.setattr(
        looped, "exit_log_probs",
        lambda z: (jnp.zeros_like(jnp.concatenate([z, z[:1]]))
                   - jnp.log(z.shape[0] + 1.0)))


@pytest.mark.parametrize("fault", [_one_round_too_few,
                                   _every_exit_weighted_alike])
def test_a_broken_timed_path_is_not_correct(tiny_ouro, monkeypatch, fault):
    fault(monkeypatch, importlib.import_module("perf.adapters.ouro"))
    result = _drive(tiny_ouro)
    assert result["correct"] is False
    assert any(limit is not None and value > limit
               for value, limit in result["compared"].values())


def test_the_references_tree_is_the_programs(tiny_ouro):
    from kungfu_tpu.models import gpt, looped
    from perf.reference import ouro as ref
    config = Manifest(tiny_ouro).cell(CELL)["config"]
    s = ref.sizes(config)
    cfg = gpt.GPTConfig(vocab_size=s["V"], d_model=s["D"], n_heads=s["H"],
                        n_layers=s["L"], d_ff=s["F"], n_kv_heads=s["Hkv"],
                        rope=True, mlp="swiglu", out_norms=True,
                        n_rounds=s["R"])
    key = jax.random.PRNGKey(0)
    shapes = lambda t: tmap(lambda x: (x.shape, x.dtype), t)
    assert shapes(jax.eval_shape(
        lambda k: looped.init_params(k, cfg), key)) == shapes(
        jax.eval_shape(lambda k: ref.init_params(k, config), key))


def test_the_references_exit_distribution_by_hand():
    from perf.reference import ouro as ref
    lam = jnp.asarray([[0.5], [0.25], [0.9]])       # the last is not read
    assert ref.exit_distribution(lam)[:, 0].tolist() == [0.5, 0.125, 0.375]


def test_the_configuration_is_the_published_one_cut_in_depth_only():
    """Every number of the catalog's row of the source's config.json is in
    the file under the same key; only the depth differs, and it is listed."""
    m = Manifest(ROOT)
    entry = m.configs["ouro2.6b-train"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    published = dict(
        head_dim=128, hidden_act="silu", hidden_size=2048,
        intermediate_size=5632, max_position_embeddings=65536,
        max_window_layers=48, model_type="ouro", num_attention_heads=16,
        num_hidden_layers=48, num_key_value_heads=16, rms_norm_eps=1e-6,
        rope_scaling=None, rope_theta=1000000, sliding_window=None,
        tie_word_embeddings=False, total_ut_steps=4, early_exit_threshold=1,
        use_sliding_window=False, vocab_size=49152,
        layer_types=["full_attention"] * 48)
    differs = sorted(k for k, v in published.items() if config.get(k) != v)
    assert differs == entry["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 48}
    assert config["num_hidden_layers"] >= 4
    assert entry["source"] == config["source"]
    for key in ("carried_state", "exit_entropy_beta", "exit_gate",
                "rotary_convention", "weights"):
        assert key in config["assumed"], key
    assert "deployment" in config
