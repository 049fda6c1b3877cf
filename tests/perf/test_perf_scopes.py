"""The reader of the trace metrics PR 27 adds, on hand-built lists and on a
small hand-made `.xplane.pb`: device time by the scopes an operation's label
names (`scope_ms`); and the manifest with the fourteen new metrics."""
import json
import os

import pytest
from jax.profiler import ProfileData

from perf import trace as tracing
from perf.manifest import Manifest
from perf.readers import scope_ms
from perf_testdata import ROOT

MS = 1_000_000
GRADS = "jit(body)/grads/while/body/closed_call"

# one device, two executions of the step; the scan's `while` encloses its body
OPS = [("while.1", 0, 9 * MS),
       ("fusion.1", 0, 3 * MS),                 # forward matmul
       ("flash_fwd.1", 3 * MS, 1 * MS),         # forward kernel
       ("fusion.2", 4 * MS, 2 * MS),            # forward again for backward
       ("fusion.3", 6 * MS, 2 * MS),            # backward: 1 ms left to while
       ("fusion.9", 9 * MS, 1 * MS),            # optimizer
       ("while.1", 12 * MS, 9 * MS),
       ("fusion.1", 12 * MS, 3 * MS), ("flash_fwd.1", 15 * MS, 1 * MS),
       ("fusion.2", 16 * MS, 2 * MS), ("fusion.3", 18 * MS, 2 * MS),
       ("fusion.9", 21 * MS, 1 * MS),
       ("fusion.1", 40 * MS, 3 * MS)]           # after the last step: outside
MODULES = [("jit_body(1)", 0, 10 * MS), ("jit_body(1)", 12 * MS, 10 * MS)]
TRACE = tracing.Trace(ops=[OPS], modules=[MODULES], host_spans=[])
LABELS = {
    "while.1": "jit(body)/grads/while [while]",
    "fusion.1": GRADS + "/jvp(ffn)/dot_general [convolution fusion]",
    "flash_fwd.1": GRADS + "/jvp(attn)/flash_fwd/pallas_call [custom-call]",
    "fusion.2": GRADS + "/transpose(jvp(jvp()))/checkpoint/"
                        "rematted_computation/ffn/dot_general [loop fusion]",
    "fusion.3": GRADS + "/transpose(jvp(jvp()))/checkpoint/ffn/dot_general"
                        " [convolution fusion]",
    "fusion.9": "jit(body)/optimizer/broadcast_in_dim [loop fusion]",
}


def args_of(metric: str) -> dict:
    with open(os.path.join(ROOT, "perf", "metrics", metric + ".json")) as f:
        args = dict(json.load(f)["args"])
    args.pop("step_pattern")
    return args


@pytest.mark.parametrize("metric, ms", [
    ("forward_ms.gpt", 4), ("backward_ms.gpt", 2), ("recompute_ms.gpt", 2),
    ("optimizer_ms.gpt", 1), ("accumulate_ms.gpt", 0),
    ("flash_fwd_ms.gpt", 1),
    ("forward_ms.resnet", 4), ("backward_ms.resnet", 2)])
def test_each_metrics_patterns_keep_their_pass(metric, ms):
    # per execution of the step, of which the window holds two
    assert scope_ms.scope_ns(TRACE, LABELS, **args_of(metric)) == 2 * ms * MS


def test_scope_time_is_self_time_inside_the_window():
    # the while counts only what its body leaves: 9 - 3 - 1 - 2 - 2
    assert scope_ms.scope_ns(TRACE, LABELS, r"/grads/while \[") == 2 * MS
    # the execution of fusion.1 at 40 ms lies outside every step
    assert scope_ms.scope_ns(TRACE, LABELS, r"jvp\(ffn\)") == 2 * 3 * MS


def test_a_fusion_counts_by_its_roots_label_and_by_its_category():
    # a pattern may name the profiler's category, which stands in brackets
    assert scope_ms.scope_ns(TRACE, LABELS, r"\[convolution") == 2 * 5 * MS
    assert scope_ms.scope_ns(TRACE, LABELS, "ffn/", r"\[convolution") \
        == 2 * 2 * MS


def test_an_operation_without_a_label_is_counted_nowhere():
    labels = {k: v for k, v in LABELS.items() if k != "fusion.1"}
    assert scope_ms.scope_ns(TRACE, labels, ".") == 2 * 7 * MS


# a fused operation, the scan's `while` around its body, a rematted pass,
# a kernel, an operation without a label, and a host plane to leave out
XSPACE = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 5 offset_ps: 0 duration_ps: 5000000000 }
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000000
             stats { metadata_id: 1 uint64_value: 7 } }
    events { metadata_id: 2 offset_ps: 2000000000 duration_ps: 1000000000 }
    events { metadata_id: 6 offset_ps: 3000000000 duration_ps: 1500000000 }
    events { metadata_id: 3 offset_ps: 5000000000 duration_ps: 1000000000 } }
  lines { name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 6000000000 } }
  event_metadata { key: 1 value { id: 1
    name: "%fusion.1 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p), kind=kOutput"
    display_name: "fusion.1"
    stats { metadata_id: 2 str_value: "jit(body)/grads/while/body/jvp(ffn)/dot_general:" }
    stats { metadata_id: 3 ref_value: 5 }
    stats { metadata_id: 6 uint64_value: 4096 } } }
  event_metadata { key: 2 value { id: 2
    name: "%flash_fwd.1 = bf16[8,128]{1,0} custom-call(bf16[8,128]{1,0} %q)"
    stats { metadata_id: 2 str_value: "jit(body)/grads/while/body/jvp(attn)/flash_fwd/pallas_call:" }
    stats { metadata_id: 3 str_value: "custom-call" } } }
  event_metadata { key: 3 value { id: 3 name: "%copy-done.1 = f32[4]{0} copy-done(%c)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_body(1)" } }
  event_metadata { key: 5 value { id: 5
    name: "%while.1 = (s32[], f32[4]{0}) while(%tuple.1), condition=%cond, body=%body"
    stats { metadata_id: 2 str_value: "jit(body)/grads/while:" }
    stats { metadata_id: 3 str_value: "while" } } }
  event_metadata { key: 6 value { id: 6
    name: "%fusion.2 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p), kind=kLoop"
    stats { metadata_id: 2 str_value: "jit(body)/grads/while/body/transpose(jvp(jvp()))/checkpoint/rematted_computation/ffn/mul:" }
    stats { metadata_id: 3 str_value: "loop fusion" } } }
  stat_metadata { key: 1 value { id: 1 name: "device_offset_ps" } }
  stat_metadata { key: 2 value { id: 2 name: "tf_op" } }
  stat_metadata { key: 3 value { id: 3 name: "hlo_category" } }
  stat_metadata { key: 5 value { id: 5 name: "convolution fusion" } }
  stat_metadata { key: 6 value { id: 6 name: "flops" } }
}
planes {
  name: "/host:CPU"
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = host thing"
    stats { metadata_id: 2 str_value: "not a device operation" } } }
  stat_metadata { key: 2 value { id: 2 name: "tf_op" } }
}
"""


@pytest.fixture(scope="module")
def xspace():
    return ProfileData.text_proto_to_serialized_xspace(XSPACE)


def test_labels_come_from_the_event_metadatas_stats(xspace):
    # `ProfileData` shows an event's own stats only; the op_name and the
    # category are stats of its metadata, read from the wire format
    body = "jit(body)/grads/while/body/"
    assert scope_ms.labels_of_xspace(xspace) == {
        "fusion.1": body + "jvp(ffn)/dot_general [convolution fusion]",
        "flash_fwd.1": body + "jvp(attn)/flash_fwd/pallas_call "
                              "[custom-call]",
        "while.1": "jit(body)/grads/while [while]",
        "fusion.2": body + "transpose(jvp(jvp()))/checkpoint/"
                           "rematted_computation/ffn/mul [loop fusion]"}
    events = [list(line.events) for plane in
              ProfileData.from_serialized_xspace(xspace).planes
              for line in plane.lines if line.name == "XLA Ops"][0]
    assert [k for k, _ in events[1].stats] == ["device_offset_ps"]


@pytest.fixture
def traced(xspace, tmp_path, monkeypatch):
    """A root with one trace under `.perf_trace/`, as the harness leaves
    it, and the context a reader gets."""
    where = tmp_path / ".perf_trace" / "cell" / "plugins" / "profile" / "t"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(xspace)
    monkeypatch.setattr(scope_ms, "ROOT", str(tmp_path))
    return {"trace": tracing.load(str(where / "host.xplane.pb"))}


@pytest.mark.parametrize("metric, ms", [
    ("forward_ms.gpt", 3.0), ("recompute_ms.gpt", 1.5),
    ("flash_fwd_ms.gpt", 1.0), ("backward_ms.gpt", None),
    ("optimizer_ms.gpt", None), ("accumulate_ms.gpt", None),
    ("flash_bwd_ms.gpt", None)])
def test_read_over_the_trace_file(traced, metric, ms):
    # nothing under such a scope (as at the parent commit) reads nothing,
    # never 0
    read, args = Manifest(ROOT).reader(metric)
    assert read is scope_ms.read
    got = read(traced, **args)
    assert got is None if ms is None else got == pytest.approx(ms)


def test_read_counts_a_whiles_self_time_only(traced):
    # 5 ms around a body of 2 + 1 + 1.5
    assert scope_ms.read(traced, include=r"/grads/while \[",
                         step_pattern=r"^jit_body\(") == pytest.approx(0.5)


def test_read_gives_nothing_without_a_step_or_a_trace(traced, tmp_path,
                                                      monkeypatch):
    assert scope_ms.read(traced, include="/grads/",
                         step_pattern=r"^jit_other\(") is None
    assert scope_ms.read({"trace": None}, include=".",
                         step_pattern=".") is None
    empty = tmp_path / "empty"
    empty.mkdir()
    monkeypatch.setattr(scope_ms, "ROOT", str(empty))
    assert scope_ms.read(traced, include=".",
                         step_pattern=r"^jit_body\(") is None


TRACE_METRICS = {
    "forward_ms.gpt", "backward_ms.gpt", "recompute_ms.gpt",
    "optimizer_ms.gpt", "accumulate_ms.gpt", "ce_head_ms.gpt",
    "flash_fwd_ms.gpt",
    "flash_bwd_ms.gpt", "forward_ms.resnet", "backward_ms.resnet"}
SETUP_METRICS = {"setup_import_s", "setup_trace_lower_s",
                 "setup_cache_load_s", "setup_compile_s"}


def test_manifest_is_sound_with_the_fourteen():
    manifest = Manifest(ROOT)
    assert manifest.problems() == []
    added = manifest.data["per_layer"][9:23]
    assert {m["name"] for m in added} == TRACE_METRICS | SETUP_METRICS
    for m in added:
        assert m["better"] == "lower"
        if m["name"] in SETUP_METRICS:
            assert (m["unit"], m["source"], m["moves"]) == (
                "s", "host_clock", "setup_s")
            assert m["workloads"] == list(manifest.cells)[:2]
        else:
            assert (m["unit"], m["source"]) == ("ms", "device_trace")
            cell, = m["workloads"]
            assert cell.startswith("mistral7b" if m["name"].endswith(".gpt")
                                   else "resnet50")
            assert m["moves"] == ("tokens_per_s" if m["name"].endswith(".gpt")
                                  else "images_per_s")


@pytest.mark.parametrize("name", sorted(TRACE_METRICS | SETUP_METRICS))
def test_each_new_metric_names_a_reader_and_its_arguments(name):
    read, args = Manifest(ROOT).reader(name)
    wanted = read.__code__.co_varnames[1:read.__code__.co_argcount]
    assert set(args) <= set(wanted)
    assert {"include", "step_pattern"} <= set(args) or set(args) == {"stage"}
