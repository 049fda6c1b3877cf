"""The trace reduction on hand-built event lists."""
import types

import pytest

from perf import trace as tracing
from perf.readers import (device_idle, kernel_roofline, module_median_ms,
                          rate_over_peak, span_mean_ms)
from perf.spans import Spans

MS = 1_000_000

# one device, two executions of a step program, 10 ms each, 2 ms apart
OPS = [("fusion.1", 0, 4 * MS), ("flash_fwd", 4 * MS, 2 * MS),
       ("fusion.2", 5 * MS, 5 * MS),            # overlaps flash by 1 ms
       ("fusion.1", 12 * MS, 4 * MS), ("flash_fwd", 16 * MS, 2 * MS),
       ("fusion.2", 19 * MS, 3 * MS)]           # 1 ms of nothing before it
MODULES = [("jit_step(1)", 0, 10 * MS), ("jit_step(1)", 12 * MS, 10 * MS)]
HOST = [("input_wait", 9 * MS, 2 * MS), ("dispatch", 11 * MS, 2 * MS),
        ("loss_fetch", 18 * MS, MS // 2)]
TRACE = tracing.Trace(ops=[OPS], modules=[MODULES], host_spans=HOST)


def test_union_of_overlapping_intervals():
    assert tracing.merge([(5, 9), (0, 4), (3, 6), (20, 21)]) == [(0, 9),
                                                                 (20, 21)]


def test_busy_counts_overlap_once_and_clips_to_the_window():
    assert tracing.busy_ns(OPS, (0, 22 * MS)) == 19 * MS
    assert tracing.busy_ns(OPS, (2 * MS, 11 * MS)) == 8 * MS


def test_idle_share():
    assert tracing.idle_share(OPS, (0, 22 * MS)) == pytest.approx(3 / 22)


def test_a_kernels_summed_time():
    assert tracing.kernel_ns(TRACE, "flash") == 4 * MS
    assert tracing.kernel_ns(TRACE, "no_such_kernel") == 0
    assert tracing.summed(OPS)[0] == ("fusion.1", 8 * MS)


def test_gaps_longest_first_and_attributed_to_the_host_span():
    found = tracing.gaps(OPS, (0, 22 * MS))
    assert found == [(10 * MS, 12 * MS), (18 * MS, 19 * MS)]
    # input_wait covers 1 ms of the first gap and dispatch 1 ms: first wins
    assert tracing.attribute(found[0], HOST) == "input_wait"
    assert tracing.attribute(found[1], HOST) == "loss_fetch"
    assert tracing.attribute((30 * MS, 31 * MS), HOST) == "none"


def test_reduce_gives_busy_window_and_breakdown():
    out = tracing.reduce(TRACE, top=2, longest=1)
    assert out["window_s"] == pytest.approx(0.022)
    assert out["busy_s"] == pytest.approx(0.019)
    assert out["breakdown"]["device_ops"] == [["fusion.1", 0.008],
                                              ["fusion.2", 0.008]]
    assert out["breakdown"]["idle_gaps"] == [["input_wait", 0.002]]


def _ctx(**kw):
    base = {"trace": TRACE, "chips": 1,
            "peaks": {"flops_bf16": 100e12, "hbm_bytes_per_s": 1e12}}
    return dict(base, **kw)


def test_device_idle_reader():
    assert device_idle.read(_ctx()) == pytest.approx(100 * 3 / 22)
    assert device_idle.read(_ctx(trace=None)) is None


def test_module_median_reader_and_nothing_to_read():
    assert module_median_ms.read(_ctx(), pattern="jit_step") == 10.0
    assert module_median_ms.read(_ctx(), pattern="jit_other") is None


def test_kernel_roofline_reader_never_returns_nought():
    config = {"hidden_size": 64, "num_attention_heads": 4,
              "num_key_value_heads": 2, "num_hidden_layers": 1}
    traffic = {"batch": 1, "seq_len": 1000}
    ctx = _ctx(config=config, traffic=traffic)
    # 6 T^2 D = 384e6 operations at 100e12 a second, against 2 ms a step
    got = kernel_roofline.read(ctx, pattern="flash",
                               min_seconds="attention_train_min_seconds",
                               step_pattern="jit_step")
    assert got == pytest.approx(100 * (384e6 / 100e12) / 2e-3)
    assert kernel_roofline.read(
        ctx, pattern="no_such_kernel",
        min_seconds="attention_train_min_seconds",
        step_pattern="jit_step") is None


def test_a_kernel_without_a_name_is_found_by_its_call_target():
    """The flash kernels carry whatever name the transformations around
    them left (`checkpoint.50`, `jvp__.16`); their HLO line names Mosaic."""
    ops = [("checkpoint.50", 0, 3 * MS), ("fusion.1", 3 * MS, 7 * MS)]
    text = {"checkpoint.50": '%checkpoint.50 = bf16[1,32,4096,128] custom-'
            'call(bf16[1,32,4096,128] %x), custom_call_target='
            '"tpu_custom_call"',
            "fusion.1": "%fusion.1 = bf16[4096] fusion(bf16[4096] %y)"}
    trace = tracing.Trace(ops=[ops], modules=[[("jit_step(1)", 0, 10 * MS)]],
                          host_spans=[], text=text)
    config = {"hidden_size": 64, "num_attention_heads": 4,
              "num_key_value_heads": 2, "num_hidden_layers": 1}
    args = dict(min_seconds="attention_train_min_seconds",
                step_pattern="jit_step")
    ctx = _ctx(trace=trace, config=config,
               traffic={"batch": 1, "seq_len": 1000})
    got = kernel_roofline.read(
        ctx, pattern='custom_call_target="tpu_custom_call"', **args)
    assert got == pytest.approx(100 * (384e6 / 100e12) / 3e-3)
    assert kernel_roofline.read(ctx, pattern="_fa_kernel", **args) is None


def test_rate_over_peak_reader():
    ctx = _ctx(rate=1000.0, traffic={},
               config={"image_size": 224, "num_filters": 64,
                       "stage_sizes": [], "num_classes": 1000})
    macs = 112 * 112 * 49 * 3 * 64 + 64 * 1000
    assert rate_over_peak.read(
        ctx, flops_per_unit="resnet_train_flops_per_image") == pytest.approx(
            100 * 1000 * 6 * macs / 100e12)


def test_span_mean_reader_counts_the_window_only():
    spans = Spans()
    spans.events = [("input_wait", 0, 5 * MS),          # set-up
                    ("input_wait", 10 * MS, 11 * MS),
                    ("input_wait", 20 * MS, 23 * MS),
                    ("dispatch", 11 * MS, 12 * MS)]
    outcome = types.SimpleNamespace(steps=2, spans=spans,
                                    window_ns=(10 * MS, 30 * MS))
    assert span_mean_ms.read({"outcome": outcome}, span="input_wait") == 2.0


def test_spans_are_laid_on_the_profiles_clock():
    spans = Spans()
    spans.events = [("input_wait", 100, 150), ("dispatch", 1_000, 1_400)]
    # the profile began at 10_900 of the time of day, which is 900 on the
    # monotonic clock: spans from 500 on, counted from there
    assert spans.on_profile_clock(10_000, 10_900, since_ns=500) == [
        ("dispatch", 100, 400)]


def test_load_reads_when_the_profile_began(tmp_path):
    """A real trace, taken here with the options the loop uses: no device
    plane on the CPU, but the profile's start on the time-of-day clock."""
    import time
    import jax
    import jax.numpy as jnp
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    before = time.time_ns()
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    finally:
        jax.profiler.stop_trace()
    trace = tracing.load(tracing.find_xplane(str(tmp_path)))
    assert before <= trace.profile_start_ns <= time.time_ns()
    assert trace.host_spans == [] and not any(trace.ops)


def test_spans_record_names_and_order():
    spans = Spans()
    with spans.span("a"):
        with spans.span("b"):
            pass
    assert [n for n, _, _ in spans.events] == ["b", "a"]
    assert all(e >= s for _, s, e in spans.events)


def test_self_time_takes_the_children_out_of_a_while():
    events = [("while.1", 0, 10 * MS), ("fusion.1", 1 * MS, 3 * MS),
              ("while.2", 4 * MS, 5 * MS), ("fusion.2", 5 * MS, 2 * MS),
              ("fusion.3", 12 * MS, 1 * MS)]
    assert dict((n, d) for n, _, d in tracing.self_times(events)) == {
        "while.1": 2 * MS, "fusion.1": 3 * MS, "while.2": 3 * MS,
        "fusion.2": 2 * MS, "fusion.3": 1 * MS}


def test_an_operation_is_named_by_what_stands_before_the_equals_sign():
    assert tracing.short(
        "%fusion.813 = (f32[4096]{0}, f32[4096,16000]{1,0}) fusion(bf16[1]"
        " %get-tuple-element.1660)") == "fusion.813"
    assert tracing.short("jit_body(94272)") == "jit_body(94272)"
