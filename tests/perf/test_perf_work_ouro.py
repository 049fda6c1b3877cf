"""The looped decoder's operation and byte counts against counts made by
hand, and the two readers that take a counts module's name."""
import json
import os

import pytest

from perf_testdata import ROOT
from perf import work, work_ouro
from perf.manifest import Manifest


def _json(rel):
    with open(os.path.join(ROOT, "perf", rel)) as f:
        return json.load(f)


OURO = _json("configs/ouro2.6b-train.json")
TRAIN = _json("traffic/train-4k-b2.json")
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def test_a_layer_is_51_38_million_weights():
    # q, k, v, o: 2048 x 2048 each (16 KV heads for 16); FFN: 3 x 2048 x 5632
    by_hand = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert work.gpt_layer_params(OURO) == by_hand == 51_380_224


def test_a_token_meets_2047_million_weights():
    # 8 layers and the head of 2048 x 49152, four times each
    by_hand = 4 * (8 * 51_380_224 + 2048 * 49152)
    assert work_ouro.ouro_matmul_params(OURO) == by_hand == 2_046_820_352
    assert work_ouro.ouro_layer_visits(OURO) == 32
    # the four heads' share of it, against 4 x 48 layers in the whole model
    head = 4 * 2048 * 49152
    assert head / by_hand == pytest.approx(0.197, abs=5e-4)
    assert head / (head + 4 * 48 * 51_380_224) == pytest.approx(0.039,
                                                                abs=5e-4)


def test_attention_of_one_layer_visit():
    # QK^T and PV forward, four products backward, half of [T, T] visible
    assert work.attention_train_flops(OURO, 4096) == 6 * 4096 ** 2 * 2048
    # group 1: k and v are as large as q
    q = 4096 * 2048
    assert work.attention_train_bytes(OURO, 4096) == 2 * 12 * q


def test_flops_per_token():
    by_hand = 6 * 2_046_820_352 + 32 * 6 * 4096 * 2048
    assert work_ouro.ouro_train_flops_per_token(OURO, TRAIN) == by_hand
    assert by_hand == pytest.approx(13.89e9, rel=1e-3)


def test_attention_roofline_is_32_visits_of_2_sequences_bound_by_compute():
    least = work_ouro.ouro_attention_train_min_seconds(OURO, TRAIN, PEAKS)
    assert least == pytest.approx(2 * 32 * 6 * 4096 ** 2 * 2048 / 197e12)


def test_the_readers_find_the_counts_by_module_name():
    m = Manifest(ROOT)
    read, kw = m.reader("step_mfu_pct.ouro")
    ctx = {"rate": 7000.0, "config": OURO, "traffic": TRAIN, "peaks": PEAKS,
           "chips": 1, "trace": None}
    assert read(ctx, **kw) == pytest.approx(
        100 * 7000.0 * work_ouro.ouro_train_flops_per_token(OURO, TRAIN)
        / 197e12)
    read, kw = m.reader("flash_roofline.ouro")
    assert read(ctx, **kw) is None          # no trace: nothing, never 0


def test_the_roofline_reader_on_a_hand_built_trace():
    from perf import trace as tracing
    from perf.readers import kernel_roofline_in
    ms = 1_000_000
    call = '%f.1 = bf16[1] custom-call(), custom_call_target="tpu_custom_call"'
    t = tracing.Trace(
        ops=[[("f.1", 0, 40 * ms), ("fusion.2", 40 * ms, 10 * ms),
              ("f.1", 100 * ms, 40 * ms)]],
        modules=[[("jit_body(1)", 0, 60 * ms), ("jit_body(1)", 100 * ms,
                                                60 * ms)]],
        host_spans=[], text={"f.1": call})
    _, kw = Manifest(ROOT).reader("flash_roofline.ouro")
    ctx = {"trace": t, "config": OURO, "traffic": TRAIN, "peaks": PEAKS}
    least = work_ouro.ouro_attention_train_min_seconds(OURO, TRAIN, PEAKS)
    assert kernel_roofline_in.read(ctx, **kw) == pytest.approx(
        100 * least / 0.040)


# labels as the chip's trace of the cell has them (my chip runs, PR 29):
# `ut_loop` stands around the scan of rounds and `ce_head` around the map
# over the rounds' heads, so jax's marks of the pass stand on those names
# and the layers' scopes inside the loop's body
FWD = "jvp(ut_loop)/while/body/closed_call/"
BWD = "transpose(jvp(ut_loop))/while/body/closed_call/"
HEAD = "jvp(ce_head)/while/body/closed_call/"


@pytest.mark.parametrize("metric, label, counted", [
    ("loop_other_ms.ouro", BWD + "add_any", True),
    ("loop_other_ms.ouro", "transpose(jvp(ut_loop))/while", True),
    ("loop_other_ms.ouro", "jvp(ut_loop)/while/body/dynamic_update_slice",
     True),
    ("loop_other_ms.ouro", "transpose(jvp(ut_loop))/while/body/dynamic_slice",
     True),
    ("loop_other_ms.ouro", FWD + "attn/flash_fwd/pallas_call", False),
    ("loop_other_ms.ouro", BWD + "checkpoint/ffn/dot_general", False),
    ("loop_other_ms.ouro", FWD + "final_norm/mul", False),
    ("loop_other_ms.ouro", HEAD + "ce_head/reduce_sum", False),
    ("loop_other_ms.ouro", "transpose(jvp(ce_head))/while", False),
    ("loop_other_ms.ouro", "jvp(exit_gate)/dot_general", False),
    ("loop_other_ms.ouro", "accumulate/add", False),
    ("loop_other_ms.ouro", "jvp()/while", False),
    ("exit_ms.ouro", "jvp(exit_gate)/rbtd,d->rbt/dot_general", True),
    ("exit_ms.ouro", "transpose(jvp(exit_mix))/add_any", True),
    ("exit_ms.ouro", FWD + "final_norm/mul", False),
    ("ce_head_ms.ouro", HEAD + "ce_head/while/body/closed_call/btd,dv->btv/"
     "dot_general", True),
    ("ce_head_ms.ouro", "transpose(jvp(ce_head))/while/body/closed_call/"
     "ce_head/btd,btv->dv/dot_general", True),
    ("ce_head_ms.ouro", "transpose(jvp(ce_head))/while/body/dynamic_slice",
     True),
    ("ce_head_ms.ouro", FWD + "ffn/dot_general", False),
    ("ce_head_other_ms.ouro", HEAD + "ce_head/exp", True),
    ("flash_fwd_ms.ouro", FWD + "attn/flash_fwd/pallas_call", True),
    ("flash_fwd_ms.ouro", BWD + "checkpoint/rematted_computation/attn/"
     "flash_fwd/pallas_call", True),
    ("flash_fwd_ms.ouro", BWD + "checkpoint/attn/flash_bwd_dq/pallas_call",
     False),
    ("flash_bwd_ms.ouro", BWD + "checkpoint/attn/flash_bwd_dkv/pallas_call",
     True),
    ("flash_bwd_ms.ouro", FWD + "attn/flash_fwd/pallas_call", False),
])
def test_the_new_scopes_patterns(metric, label, counted):
    from perf import trace as tracing
    from perf.readers import scope_ms
    ms = 1_000_000
    t = tracing.Trace(ops=[[("fusion.1", 0, 3 * ms)]],
                      modules=[[("jit_body(1)", 0, 5 * ms)]], host_spans=[])
    args = dict(_json(f"metrics/{metric}.json")["args"])
    args.pop("step_pattern")
    labels = {"fusion.1": "jit(body)/grads/while/body/closed_call/"
                          f"{label} [loop fusion]"}
    assert scope_ms.scope_ns(t, labels, **args) == (3 * ms if counted else 0)
