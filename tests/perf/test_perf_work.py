"""The operation and byte counts against counts made by hand."""
import json
import os

import pytest

from perf_testdata import ROOT
from perf import work


def _json(rel):
    with open(os.path.join(ROOT, "perf", rel)) as f:
        return json.load(f)


MISTRAL = _json("configs/mistral7b-train.json")
RESNET = _json("configs/resnet50.json")
TRAIN_4K = _json("traffic/train-4k.json")


def test_mistral_layer_is_218_1_million_weights():
    # q, o: 4096 x 4096 each; k, v: 4096 x 1024 each; FFN: 3 x 4096 x 14336
    by_hand = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert work.gpt_layer_params(MISTRAL) == by_hand == 218_103_808


def test_mistral_two_layers_and_head():
    assert work.gpt_matmul_params(MISTRAL) == (2 * 218_103_808
                                               + 4096 * 32000)


def test_mistral_flops_per_token():
    # 6 per weight, and 6 T D per layer of causal attention
    by_hand = 6 * 567_279_616 + 2 * 6 * 4096 * 4096
    assert work.gpt_train_flops_per_token(MISTRAL, TRAIN_4K) == by_hand
    assert by_hand == pytest.approx(3.605e9, rel=1e-3)


def test_resnet50_is_4_09_gmac_an_image():
    assert work.resnet_forward_macs(RESNET) == pytest.approx(4.09e9,
                                                             rel=2e-3)
    assert work.resnet_train_flops_per_image(RESNET, {}) == (
        6 * work.resnet_forward_macs(RESNET))


def test_resnet_stem_and_classifier_by_hand():
    stem_only = dict(RESNET, stage_sizes=[])
    # 112 x 112 outputs of a 7 x 7 x 3 -> 64 convolution, then 64 -> 1000
    assert work.resnet_forward_macs(stem_only) == (
        112 * 112 * 49 * 3 * 64 + 64 * 1000)


def test_attention_work_of_one_sequence():
    # QK^T and PV forward, four products backward, half of [T, T] visible:
    # 6 products x T^2/2 x D multiply-accumulates x 2
    assert work.attention_train_flops(MISTRAL, 4096) == 6 * 4096 ** 2 * 4096
    q, kv = 4096 * 4096, 4096 * 1024
    assert work.attention_train_bytes(MISTRAL, 4096) == 2 * (6 * q + 6 * kv)


def test_attention_roofline_is_bound_by_compute_at_4k():
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    least = work.attention_train_min_seconds(MISTRAL, TRAIN_4K, peaks)
    assert least == pytest.approx(8 * 6 * 4096 ** 3 / 197e12)
