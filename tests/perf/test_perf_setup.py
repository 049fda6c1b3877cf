"""The four `setup_*` metrics: what the program counted of its own set-up,
read at the window's opening (`perf/readers/setup_stage_s.py`), over a tiny
run of the benchmark's own loop on the CPU."""
import time

import jax
import pytest

import kungfu_tpu
from kungfu_tpu.comm.mesh import flat_mesh
from kungfu_tpu.data.pipeline import Prefetcher
from kungfu_tpu.utils import compile_cache
from perf import loop, program, run, traffic_gen
from perf.manifest import Manifest
from perf.readers import setup_stage_s

STAGES = ["import", "trace_lower", "cache_load", "compile"]


@pytest.fixture(scope="module")
def ran(tiny_root):
    """(the run's context, its counter) of one tiny ResNet run."""
    cell = Manifest(tiny_root).cell("resnet50-train-b256")
    started = time.perf_counter()
    counter = compile_cache.CompileCounter()
    job = program.build(cell["config"], cell["traffic"],
                        flat_mesh(jax.devices()[:1]))
    pool = traffic_gen.make_pool(cell["config"], cell["traffic"], 5)
    prefetcher = Prefetcher(traffic_gen.cycle(pool), depth=2,
                            place=job.place)
    try:
        out = loop.run(job, run.key_of(5), prefetcher, 0.2, started, 3,
                       counter=counter)
    finally:
        prefetcher.close()
    return {"outcome": out}, counter


def read(ctx, stage):
    reader, args = Manifest().reader(f"setup_{stage}_s")
    assert reader is setup_stage_s.read and args == {"stage": stage}
    return reader(ctx, **args)


def test_the_stages_add_up_to_less_than_the_set_up(ran):
    ctx, counter = ran
    assert compile_cache.current_counter() is counter
    got = {stage: read(ctx, stage) for stage in STAGES}
    assert got["import"] == kungfu_tpu.import_seconds > 0
    assert got["trace_lower"] > 0 and got["compile"] > 0
    assert got["cache_load"] == 0       # the CPU has no cache: all compiled
    # the import lies before this run's start, the rest inside its set-up.
    # At this size a set-up is little else than these stages (on the chip
    # they are 20 of 30 s), and the feed's thread may compile a placement
    # while the main thread traces: hence the twentieth of room
    assert max(got.values()) < ctx["outcome"].setup_s
    assert (sum(got.values()) - got["import"]
            < 1.05 * ctx["outcome"].setup_s)


@pytest.mark.parametrize("event", ["TRACE", "LOWER", "RETRIEVAL", "REQUEST"])
def test_what_follows_the_windows_opening_is_left_out(ran, event):
    # the reference's compile, which follows the window
    ctx, counter = ran
    before = {stage: read(ctx, stage) for stage in STAGES}
    jax.monitoring.record_event_duration_secs(getattr(counter, event), 5.0)
    assert {stage: read(ctx, stage) for stage in STAGES} == before


@pytest.mark.parametrize("stage", STAGES)
def test_a_program_without_the_counters_reads_nothing(ran, stage,
                                                      monkeypatch):
    # the parent commit under this PR's benchmark files
    ctx, _ = ran
    monkeypatch.delattr(compile_cache, "current_counter")
    monkeypatch.delattr(kungfu_tpu, "import_seconds")
    assert read(ctx, stage) is None


def test_no_counter_made_yet_reads_nothing(ran, monkeypatch):
    monkeypatch.setattr(compile_cache, "_current", None)
    assert read(ran[0], "compile") is None


def test_an_unknown_stage_is_refused(ran):
    with pytest.raises(ValueError):
        setup_stage_s.read(ran[0], "link")
