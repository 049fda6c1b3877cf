"""`correct` at a size the CPU holds: each cell's program agrees with its
plain reference; the reference computed one precision lower, put in the
program's place, does not; and a run whose timed path is broken underneath
comes out as not correct."""
import importlib
import time

import jax
import jax.numpy as jnp
import pytest

from perf import compare, run, traffic_gen
from perf.manifest import Manifest
from perf.reference import train as reference

PEAKS = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11}
CELLS = ["mistral7b-train-4k", "resnet50-train-b256"]
tmap = jax.tree_util.tree_map


def _drive(root, cell, seed=7, seconds=0.5):
    """A run with the look for a chip skipped."""
    m = Manifest(root)
    code, result = run.drive(m, m.cell(cell), seed, seconds, 0,
                             jax.devices(), PEAKS, time.perf_counter())
    assert code == 0
    return result


@pytest.mark.parametrize("cell", CELLS)
def test_the_program_agrees_with_its_reference(tiny_root, cell):
    # a seed past 2**31, as the driver's are
    result = _drive(tiny_root, cell, seed=2 ** 31 + 12345)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "compared"
    for name, (value, limit) in result["compared"].items():
        assert limit is None or value <= limit, name
    assert set(result["metrics"]) == {
        "setup_s", Manifest(tiny_root).cell(cell)["traffic"]["rate_metric"]}


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_one_precision_lower_is_not_correct(tiny_root, cell):
    c = Manifest(tiny_root).cell(cell)
    config, traffic = c["config"], c["traffic"]
    pool = traffic_gen.make_pool(config, traffic, 3)[:traffic[
        "reference_steps"]]
    key = run.key_of(3)
    args = (config["family"], config, traffic["optimizer"], key, pool)
    want = reference.follow(*args)
    lower = reference.follow(*args, quant="fp8")
    ok, compared = compare.decide(compare.numbers(lower, want), c["limits"])
    assert not ok, compared
    same, _ = compare.decide(compare.numbers(want, want), c["limits"])
    assert same


def _unchanged(job):
    """A step that returns its state unchanged (the real one runs on a copy,
    since it donates what it is given)."""
    real = job.step

    def step(state, batch):
        _, loss = real(tmap(jnp.copy, state), batch)
        return state, loss
    job.step = step


def _half_batch(job):
    """Half of the batch left out, the mean taken over the rest: the first
    half stands in for the second."""
    real = job.step

    def step(state, batch):
        half = tmap(lambda a: jnp.concatenate(
            [a[:a.shape[0] // 2]] * 2), batch)
        return real(state, half)
    job.step = step


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_unchanged, _half_batch])
def test_a_broken_timed_path_is_not_correct(tiny_root, monkeypatch, cell,
                                            fault):
    family = Manifest(tiny_root).cell(cell)["config"]["family"]
    adapter = importlib.import_module("perf.adapters." + family)
    build = adapter.build

    def broken(config, traffic, mesh):
        job = build(config, traffic, mesh)
        fault(job)
        return job
    monkeypatch.setattr(adapter, "build", broken)
    result = _drive(tiny_root, cell)
    assert result["correct"] is False
    assert any(limit is not None and value > limit
               for value, limit in result["compared"].values())


def test_the_references_tree_is_the_programs(tiny_root):
    """The weights the benchmark makes carry the names and shapes the
    program's own initialisers give."""
    from kungfu_tpu.models import gpt, resnet
    from perf.reference import gpt as ref_gpt, resnet as ref_resnet
    m = Manifest(tiny_root)
    key = jax.random.PRNGKey(0)

    config = m.cell("mistral7b-train-4k")["config"]
    s = ref_gpt.sizes(config)
    cfg = gpt.GPTConfig(vocab_size=s["V"], d_model=s["D"], n_heads=s["H"],
                        n_layers=s["L"], d_ff=s["F"], n_kv_heads=s["Hkv"],
                        rope=True, mlp="swiglu")
    shapes = lambda t: tmap(lambda x: (x.shape, x.dtype), t)
    assert shapes(jax.eval_shape(lambda k: gpt.init_params(k, cfg), key)) == shapes(
        jax.eval_shape(lambda k: ref_gpt.init_params(k, config), key))

    config = m.cell("resnet50-train-b256")["config"]
    model = resnet.ResNet(stage_sizes=config["stage_sizes"],
                          num_classes=config["num_classes"],
                          num_filters=config["num_filters"])
    size = config["image_size"]
    variables = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((2, size, size, 3))), key)
    assert shapes(variables["params"]) == shapes(jax.eval_shape(
        lambda k: ref_resnet.init_params(k, config), key))
    assert shapes(variables["batch_stats"]) == shapes(
        jax.eval_shape(lambda: ref_resnet.init_model_state(config)))


def test_worst_leaf_gap_is_a_gap_of_norms_against_the_larger_of_two():
    want = [1.0, 2.0, 4.0, 0.001]
    # the small leaf is measured against the median leaf (1.5), not itself
    assert compare.worst_leaf_gap([1.0, 2.0, 4.0, 0.004], want) == (
        pytest.approx(0.003 / 1.5))
    assert compare.worst_leaf_gap([1.0, 2.2, 4.0, 0.001], want) == (
        pytest.approx(0.1))
    assert compare.worst_leaf_gap([1.0, 2.0], want) == float("inf")
    assert compare.worst_leaf_gap([1.0, float("nan"), 4.0, 0.001],
                                  want) == float("inf")


def test_median_leaf_gap_does_not_follow_one_leaf():
    want = [1.0, 2.0, 4.0, 8.0, 16.0]
    got = [1.0, 2.2, 4.0, 8.0, 32.0]
    assert compare.worst_leaf_gap(got, want) == pytest.approx(1.0)
    assert compare.median_leaf_gap(got, want) == 0.0
    assert compare.median_leaf_gap([2 * x for x in want], want) == (
        pytest.approx(1.0))
    assert compare.median_leaf_gap([1.0], want) == float("inf")


def test_leaves_without_a_gradient_are_left_out_of_the_change():
    ref = {"losses": [2.0], "grad_norms": [1.0, 1.0, 1e-9],
           "change_norms": [0.5, 0.5, 0.5], "state_norms": []}
    got = {"losses": [2.0], "grad_norms": [1.0, 1.0, 1e-9],
           "change_norms": [0.5, 0.5, 0.0], "state_norms": []}
    assert compare.numbers(got, ref)["change"] == 0.0
    got["change_norms"] = [0.5, 0.0, 0.5]       # a leaf that did not move
    assert compare.numbers(got, ref)["change"] == 1.0


def test_a_number_without_a_limit_is_refused():
    with pytest.raises(KeyError):
        compare.decide({"grad": 0.1}, {"loss1": 0.1})
    assert compare.decide({"grad": 0.1}, {"grad": None}) == (
        True, {"grad": [0.1, None]})
    assert compare.decide({"grad": 0.1}, {"grad": 0.05})[0] is False
