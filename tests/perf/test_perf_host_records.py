"""The host records' metrics (`perf/readers/host_records.py`): the feed's
staging and waits, the window's stalls and pauses, set-up's collections. Over
a tiny run of the benchmark's own loop on the CPU, and on hand-built records
where a number has to come out exactly."""
import collections
import time
import types

import jax
import pytest

from kungfu_tpu.comm.mesh import flat_mesh
from kungfu_tpu.data.pipeline import Prefetcher
from kungfu_tpu.utils import compile_cache
from kungfu_tpu.utils.compile_cache import CompileCounter, HostRecord
from perf import loop, program, run, traffic_gen
from perf.manifest import Manifest
from perf.readers import host_records
from perf.spans import Spans, clock_offset_ns
from perf_testdata import ROOT

QUANTITIES = {"input_stage_ms": "input_stage",
              "input_wait_in_ms": "input_wait",
              "window_stall_ms": "window_stall",
              "window_pause_ms": "window_pause", "setup_gc_s": "setup_gc"}
NEW = ["input_stage_ms", "input_stage_ms.resnet", "input_wait_in_ms",
       "input_wait_in_ms.resnet", "window_stall_ms", "window_stall_ms.resnet",
       "window_pause_ms", "window_pause_ms.resnet", "setup_gc_s"]
MS, S = 10 ** 6, 10 ** 9


@pytest.fixture(scope="module")
def ran(tiny_root):
    """(the run's context, its counter) of one tiny ResNet run."""
    cell = Manifest(tiny_root).cell("resnet50-train-b256")
    started = time.perf_counter()
    counter = CompileCounter()
    job = program.build(cell["config"], cell["traffic"],
                        flat_mesh(jax.devices()[:1]))
    pool = traffic_gen.make_pool(cell["config"], cell["traffic"], 7)
    prefetcher = Prefetcher(traffic_gen.cycle(pool), depth=2,
                            place=job.place)
    try:
        out = loop.run(job, run.key_of(7), prefetcher, 0.3, started, 3,
                       counter=counter)
    finally:
        prefetcher.close()
    del prefetcher             # as the benchmark does before its readers
    return {"outcome": out}, counter


def read(ctx, name):
    reader, args = Manifest().reader(name)
    assert reader is host_records.read
    return reader(ctx, **args)


def test_the_nine_are_appended_and_sound():
    m = Manifest(ROOT)
    assert m.problems() == []
    added = m.data["per_layer"][-len(NEW):]
    assert [x["name"] for x in added] == NEW
    for x in added:
        assert x["source"] == "program_span" and x["better"] == "lower"
        base = x["name"].split(".")[0]
        assert Manifest().reader(x["name"])[1] == {
            "quantity": QUANTITIES[base]}
        assert x["moves"] == ("setup_s" if base == "setup_gc_s" else
                              "images_per_s" if x["name"].endswith(".resnet")
                              else "tokens_per_s")


@pytest.mark.parametrize("name", NEW)
def test_each_reads_a_number_over_a_run(ran, name, capsys):
    ctx, counter = ran
    value = read(ctx, name)
    assert isinstance(value, float) and value >= 0
    if name.startswith("window_stall"):
        assert "longest interval between hand-outs" in capsys.readouterr().err


def test_the_windows_hand_outs_are_its_steps_and_their_stagings(ran):
    ctx, counter = ran
    out = ctx["outcome"]
    handed = host_records.handed_out(list(counter.host), counter.HANDOUT,
                                     *out.window_ns)
    assert len(handed) == out.steps > 1
    staged = {r.seq: r for r in counter.host if r.kind == counter.STAGE}
    assert all(staged[r.seq].end_ns <= r.end_ns for r in handed)
    assert read(ctx, "input_stage_ms") == pytest.approx(sum(
        staged[r.seq].end_ns - staged[r.seq].start_ns
        for r in handed) / len(handed) / MS)
    # the program's wait is inside the loop's span around the same call
    assert read(ctx, "input_wait_in_ms") <= out.spans.total_ns(
        "input_wait", *out.window_ns) / out.steps / MS


def test_set_up_collections_lie_before_the_window(ran):
    ctx, counter = ran
    assert read(ctx, "setup_gc_s") < ctx["outcome"].setup_s


def test_a_program_record_and_a_loop_span_share_the_traces_clock(
        ran, monkeypatch):
    """Laid on a profiler trace's clock with one offset, as the loop lays
    its spans, a record and a span of one instant land on one time, and
    each hand-out lies inside the loop's `input_wait` span of its call."""
    ctx, counter = ran
    out = ctx["outcome"]
    offset, began = clock_offset_ns(), out.window_ns[0]
    waits = [(s, d) for n, s, d in out.spans.on_profile_clock(
        offset, began + offset) if n == "input_wait"]
    handed = [(s, d) for label, s, d in counter.on_profile_clock(
        offset, began + offset, since_ns=out.spans.events[0][1])
        if label == counter.HANDOUT]
    assert len(handed) == len(waits)
    for (s, d), (ws, wd) in zip(handed, waits):
        assert ws <= s and s + d <= ws + wd
    instant = time.perf_counter_ns()
    monkeypatch.setattr(compile_cache, "_current", counter)  # kept current
    monkeypatch.setattr(time, "perf_counter_ns", lambda: instant)
    spans, fresh = Spans(), CompileCounter()
    with spans.span("dispatch"):
        fresh.add(fresh.STAGE, "", time.perf_counter_ns(),
                  time.perf_counter_ns(), 0)
    [laid] = [s for label, s, _ in fresh.on_profile_clock(offset, 5)
              if label == fresh.STAGE]
    assert spans.on_profile_clock(offset, 5)[0][1] == laid


def handout(seq, at_ns, wait_ns=MS // 10):
    return HostRecord(CompileCounter.HANDOUT, "", at_ns - wait_ns, at_ns,
                      seq, 1)


@pytest.fixture
def hand_built(monkeypatch):
    """Put hand-built records where the readers look, in a counter that no
    listener writes to."""
    def install(records, window_ns, steps=0):
        counter = CompileCounter.__new__(CompileCounter)
        counter.host = collections.deque(records)
        monkeypatch.setattr(compile_cache, "current_counter",
                            lambda: counter)
        outcome = types.SimpleNamespace(window_ns=window_ns, spans=Spans(),
                                        steps=steps, setup_s=1.0)
        return {"outcome": outcome}
    return install


def pulse(step_ns, n, stall_at=None, stall_ns=0, at=S):
    """Hand-out moments every `step_ns`, the one after `stall_at` late by
    `stall_ns`."""
    moments, t = [], at
    for i in range(n):
        t += step_ns + (stall_ns if i == stall_at else 0)
        moments.append(t)
    return moments


def test_one_long_interval_reads_its_excess_over_the_median(hand_built,
                                                            capsys):
    # a window of 590-ms steps in which one interval took 1.9 s
    moments = pulse(590 * MS, 32, stall_at=12, stall_ns=1310 * MS)
    ctx = hand_built([handout(i, t) for i, t in enumerate(moments)],
                     (S, moments[-1] + S))
    assert read(ctx, "window_stall_ms") == pytest.approx(1310.0)
    line = capsys.readouterr().err
    assert "longest interval between hand-outs 1900.000 ms" in line
    assert "feed.handout covers 0.100 ms" in line       # its own wait only
    assert read(ctx, "input_wait_in_ms") == pytest.approx(0.1)


def test_a_clean_pulse_reads_no_stall(hand_built):
    moments = pulse(98 * MS, 200)
    ctx = hand_built([handout(i, t) for i, t in enumerate(moments)],
                     (S, moments[-1] + S))
    assert read(ctx, "window_stall_ms") == 0
    assert host_records.stall_ns([]) == host_records.stall_ns([5]) == 0


def test_the_stall_line_names_what_covers_the_longest_interval(hand_built,
                                                               capsys):
    moments = pulse(100 * MS, 10, stall_at=4, stall_ns=300 * MS)
    long_from = moments[3]
    gc2 = HostRecord(CompileCounter.GC, "gen2", long_from + 10 * MS,
                     long_from + 260 * MS, -1, 9)
    ctx = hand_built([handout(i, t) for i, t in enumerate(moments)] + [gc2],
                     (S, moments[-1] + S))
    assert read(ctx, "window_stall_ms") == pytest.approx(300.0)
    assert "gc gen2 covers 250.000 ms of it (62.5%)" in capsys.readouterr().err


def test_hand_outs_outside_the_window_are_left_out(hand_built):
    moments = pulse(100 * MS, 10, stall_at=1, stall_ns=5 * S)
    records = [handout(i, t) for i, t in enumerate(moments)]
    assert read(hand_built(records, (moments[0], moments[-1] + 1)),
                "window_stall_ms") == pytest.approx(5000.0)
    assert read(hand_built(records, (moments[1], moments[-1] + 1)),
                "window_stall_ms") == 0


def test_the_pauses_are_a_union_clipped_to_the_window(hand_built, capsys):
    lo, hi = 10 * S, 20 * S
    kinds = CompileCounter.JAX_KINDS
    records = [
        HostRecord(CompileCounter.GC, "gen1", 9 * S, lo + 100 * MS),
        HostRecord(CompileCounter.GC, "gen2", 12 * S, 12 * S + 300 * MS),
        HostRecord(kinds[CompileCounter.TRACE], "step", 12 * S + 200 * MS,
                   12 * S + 500 * MS),
        HostRecord(kinds[CompileCounter.RETRIEVAL], "", 15 * S,
                   15 * S + 40 * MS),
        HostRecord(kinds[CompileCounter.REQUEST], "jit(step)", 15 * S,
                   15 * S + 50 * MS),
        HostRecord(CompileCounter.STAGE, "", 16 * S, 17 * S, 3),  # no pause
        HostRecord(CompileCounter.GC, "gen2", hi - 10 * MS, hi + S),
    ]
    ctx = hand_built(records, (lo, hi))
    assert read(ctx, "window_pause_ms") == pytest.approx(
        100 + 500 + 50 + 10)
    line = capsys.readouterr().err
    assert "gc gen2 x2 310.000 ms" in line
    assert "programs requested: jit(step) from the cache" in line


def test_set_up_collections_are_read_up_to_the_windows_opening(hand_built):
    lo = 30 * S
    records = [HostRecord(CompileCounter.GC, "gen2", 2 * S, 2 * S + 80 * MS),
               HostRecord(CompileCounter.GC, "gen1", 2 * S + 50 * MS,
                          2 * S + 90 * MS),
               HostRecord(CompileCounter.GC, "gen2", lo - 20 * MS,
                          lo + 20 * MS),
               HostRecord(CompileCounter.GC, "gen2", lo + S, lo + 2 * S),
               HostRecord(CompileCounter.JAX_KINDS[CompileCounter.LOWER],
                          "jit(step)", 3 * S, 10 * S)]
    ctx = hand_built(records, (lo, lo + 20 * S))
    assert read(ctx, "setup_gc_s") == pytest.approx(0.090 + 0.020)


def test_staging_is_read_for_the_batches_handed_out_in_the_window(
        hand_built):
    lo, hi = 10 * S, 20 * S
    records = [HostRecord(CompileCounter.STAGE, "", 9 * S, 9 * S + 7 * MS, 1),
               handout(1, 9 * S + 50 * MS),
               HostRecord(CompileCounter.STAGE, "", 11 * S, 11 * S + 3 * MS,
                          2),
               HostRecord(CompileCounter.STAGE, "", 12 * S, 12 * S + 5 * MS,
                          3),
               handout(2, 12 * S), handout(3, 13 * S),
               HostRecord(CompileCounter.STAGE, "", 19 * S, 19 * S + 9 * MS,
                          4)]                     # handed out after the window
    ctx = hand_built(records, (lo, hi))
    assert read(ctx, "input_stage_ms") == pytest.approx(4.0)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_records_reads_nothing(ran, name,
                                                     monkeypatch):
    # an older program under these benchmark files: a counter with no host
    # records, and a package with no counter at all
    ctx, counter = ran
    monkeypatch.setattr(compile_cache, "current_counter",
                        lambda: types.SimpleNamespace(records=[]))
    assert read(ctx, name) is None
    monkeypatch.delattr(compile_cache, "current_counter")
    assert read(ctx, name) is None


def test_no_hand_out_in_the_window_reads_nothing(hand_built):
    ctx = hand_built([handout(1, 5 * S)], (10 * S, 20 * S))
    assert read(ctx, "window_stall_ms") is None
    assert read(ctx, "input_wait_in_ms") is None


def test_an_unknown_quantity_is_refused(ran):
    with pytest.raises(ValueError):
        host_records.read(ran[0], "dispatch")
