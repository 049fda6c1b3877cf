"""Where the persistent XLA compile cache lives (utils/compile_cache.py).

``JAX_COMPILATION_CACHE_DIR`` wins and then the code sets no directory;
without it an accelerator gets ``<checkout>/.jax_cache`` — a path made
from the package's location, the same in every process — and the CPU
gets no cache at all.
"""
import gc
import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import pytest

from kungfu_tpu.utils import compile_cache as cc
from kungfu_tpu.utils.compile_cache import CompileCounter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Restore the jax cache options a test changed."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_compilation_cache_include_metadata_in_key")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)


def test_off_on_cpu_by_default(monkeypatch, cache_config):
    monkeypatch.delenv(cc.CACHE_DIR_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert cc.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_dot_jax_cache_of_the_checkout(monkeypatch,
                                                  cache_config):
    monkeypatch.delenv(cc.CACHE_DIR_ENV, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert cc.DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
    first = cc.enable_compile_cache()
    assert first == cc.DEFAULT_DIR
    assert jax.config.jax_compilation_cache_dir == cc.DEFAULT_DIR
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    # a second call in the same process answers the same path
    assert cc.enable_compile_cache() == first
    # under the checkout, not the old home-directory default
    assert os.path.dirname(first) == REPO


def test_env_var_wins_and_code_sets_no_directory(monkeypatch,
                                                 cache_config, tmp_path):
    """With the variable set, jax already has the directory from its
    own import-time read; the helper must not touch that option — not
    even on a backend that would otherwise get the default."""
    monkeypatch.setenv(cc.CACHE_DIR_ENV, str(tmp_path))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, val: (updates.append(name), real_update(name, val)))
    assert cc.enable_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in updates


def test_env_thresholds_are_respected(monkeypatch, cache_config, tmp_path):
    monkeypatch.setenv(cc.CACHE_DIR_ENV, str(tmp_path))
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "7")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 7.0)
    cc.enable_compile_cache()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 7.0


_CHILD = r"""
import json, sys
import jax, jax.numpy as jnp
from kungfu_tpu.utils.compile_cache import (CompileCounter,
                                            enable_compile_cache)
path = enable_compile_cache()
counter = CompileCounter()
jax.jit(lambda x: jnp.tanh(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()
print(json.dumps({"path": path, "config": jax.config.jax_compilation_cache_dir,
                  "compiled": counter.compiled, "hits": counter.cache_hits,
                  "trace": counter.seconds(counter.TRACE),
                  "lower": counter.seconds(counter.LOWER),
                  "request": counter.seconds(counter.REQUEST),
                  "retrieval": counter.seconds(counter.RETRIEVAL),
                  "compile": counter.compile_seconds(),
                  "events": [name for name, _, _ in counter.records]}))
"""


def _child(env, script=_CHILD):
    r = subprocess.run([sys.executable, "-c", script], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_two_processes_share_the_env_directory(tmp_path):
    """Same path in two processes; the second compiles nothing (the
    counter chip_smoke.py prints)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    cold, warm = _child(env), _child(env)
    assert cold["path"] == warm["path"] == str(tmp_path)
    assert cold["config"] == warm["config"] == str(tmp_path)
    assert cold["compiled"] >= 1 and cold["hits"] == 0
    assert warm["compiled"] == 0 and warm["hits"] == cold["compiled"]
    assert os.listdir(tmp_path)


def test_the_counter_says_what_each_stage_took(tmp_path):
    """Cold, the backend's seconds are compiling; warm, they are the
    cache's retrieval and compiling reads 0: the benchmark's
    `setup_compile_s` and `setup_cache_load_s`."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    cold, warm = _child(env), _child(env)
    for run in (cold, warm):
        assert run["trace"] > 0 and run["lower"] > 0 and run["request"] > 0
        assert set(run["events"]) <= set(CompileCounter.STAGE_EVENTS)
    assert cold["retrieval"] == 0 and cold["compile"] == cold["request"]
    assert warm["compiled"] == 0 and warm["compile"] == 0
    assert 0 < warm["retrieval"] <= warm["request"]
    # a hit's retrieval arrives just before the request that holds it
    at = warm["events"].index(CompileCounter.RETRIEVAL)
    assert warm["events"][at + 1] == CompileCounter.REQUEST


def _feed(event, seconds, at_ns, monkeypatch):
    monkeypatch.setattr(cc.time, "perf_counter_ns", lambda: at_ns)
    jax.monitoring.record_event_duration_secs(event, seconds)


def test_the_counter_sums_spans_up_to_a_moment(monkeypatch):
    counter = CompileCounter()
    assert cc.current_counter() is counter
    S = 10 ** 9
    # an inner jit's tracing inside its caller's, then one apart
    _feed(counter.TRACE, 1.0, 4 * S, monkeypatch)
    _feed(counter.TRACE, 3.0, 5 * S, monkeypatch)
    _feed(counter.TRACE, 0.5, 9 * S, monkeypatch)
    _feed(counter.LOWER, 0.25, 10 * S, monkeypatch)
    _feed("/jax/some/other_duration", 7.0, 10 * S, monkeypatch)
    assert [name for name, _, _ in counter.records] == [
        counter.TRACE] * 3 + [counter.LOWER]
    assert counter.seconds(counter.TRACE, counter.LOWER) == \
        pytest.approx(3.75)
    assert counter.seconds(counter.TRACE) == pytest.approx(3.5)
    assert counter.seconds(counter.TRACE, until_ns=5 * S) == \
        pytest.approx(3.0)
    assert counter.seconds(counter.TRACE, until_ns=3 * S) == 0
    assert counter.seconds(counter.LOWER) == pytest.approx(0.25)
    # jax traces again while it lowers: over both events, counted once
    _feed(counter.TRACE, 0.125, 10 * S - S // 16, monkeypatch)
    assert counter.seconds(counter.TRACE, counter.LOWER) == \
        pytest.approx(3.75)
    # one program from the cache, one compiled, one compiled later
    _feed(counter.RETRIEVAL, 0.5, 11 * S, monkeypatch)
    _feed(counter.REQUEST, 0.75, 11 * S, monkeypatch)
    _feed(counter.REQUEST, 20.0, 40 * S, monkeypatch)
    _feed(counter.REQUEST, 2.0, 60 * S, monkeypatch)
    assert counter.seconds(counter.RETRIEVAL) == pytest.approx(0.5)
    assert counter.compile_seconds() == pytest.approx(22.0)
    assert counter.compile_seconds(until_ns=50 * S) == pytest.approx(20.0)
    assert counter.compile_seconds(until_ns=11 * S) == 0


def test_the_counters_records_are_bounded(monkeypatch):
    monkeypatch.setattr(CompileCounter, "MAX_RECORDS", 3)
    counter = CompileCounter()
    for i in range(5):
        _feed(counter.LOWER, 1.0, (i + 1) * 10 ** 10, monkeypatch)
    assert len(counter.records) == 3
    assert counter.seconds(counter.LOWER) == pytest.approx(3.0)


def test_the_newest_counter_is_the_current_one():
    first, second = CompileCounter(), CompileCounter()
    assert cc.current_counter() is second is not first


def test_names_are_in_the_caches_key_unless_the_environment_says(
        monkeypatch, cache_config, tmp_path):
    """A program whose scopes were renamed must not load the executable
    compiled under the old names (docs/monitoring.md)."""
    option = "jax_compilation_cache_include_metadata_in_key"
    monkeypatch.setenv(cc.CACHE_DIR_ENV, str(tmp_path))
    monkeypatch.delenv(option.upper(), raising=False)
    jax.config.update(option, False)
    cc.enable_compile_cache()
    assert getattr(jax.config, option) is True
    monkeypatch.setenv(option.upper(), "0")
    jax.config.update(option, False)
    cc.enable_compile_cache()
    assert getattr(jax.config, option) is False


def test_the_package_says_how_long_its_import_took():
    """0.4 s alone on this box once jax is loaded (conftest.py has), 2.9 s
    with it: the benchmark's `setup_import_s` reads the attribute."""
    import kungfu_tpu
    assert 0 < kungfu_tpu.import_seconds < 5


def test_no_knob_and_no_home_directory_default():
    """The package's own cache knob is gone (one place decides, from
    outside), and no code names the old home-directory default."""
    from kungfu_tpu.utils import knobs
    assert not [k for k in knobs.KNOBS if "COMPILE_CACHE" in k]
    src = open(cc.__file__).read()
    assert "expanduser" not in src and "knobs" not in src


@pytest.fixture
def no_automatic_gc():
    """Collections only where the test calls `gc.collect()`."""
    was = gc.isenabled()
    gc.disable()
    yield
    if was:
        gc.enable()


def _collections(counter, gen="gen2"):
    return [r for r in counter.host if r.kind == counter.GC and r.name == gen]


YOUNG = {"generation": 0, "collected": 0, "uncollectable": 0}
OLDER = dict(YOUNG, generation=1, collected=5)


def test_a_collection_leaves_one_record(no_automatic_gc):
    counter = CompileCounter()
    before = len(_collections(counter))
    cycles = [[] for _ in range(1000)]
    for a, b in zip(cycles, cycles[1:]):
        a.append(b)
        b.append(a)
    del cycles, a, b
    gc.collect()
    after = _collections(counter)
    assert len(after) == before + 1
    assert after[-1].start_ns <= after[-1].end_ns and after[-1].value >= 1000


def test_a_newer_counter_takes_the_collections_over(no_automatic_gc):
    first, second = CompileCounter(), CompileCounter()
    assert gc.callbacks.count(cc._on_gc) == 1
    had = len(_collections(first)), len(_collections(second))
    gc.collect()
    assert (len(_collections(first)), len(_collections(second))) == (
        had[0], had[1] + 1)


def test_a_quick_young_collection_allocates_and_records_nothing(
        no_automatic_gc):
    counter = CompileCounter()
    kept = len(counter.host)
    allocated = gc.get_count()[0]
    cc._on_gc("start", YOUNG)
    cc._on_gc("stop", YOUNG)
    assert gc.get_count()[0] == allocated and len(counter.host) == kept
    cc._on_gc("start", OLDER)
    cc._on_gc("stop", OLDER)
    assert len(counter.host) == kept + 1
    assert counter.host[-1][:2] == (counter.GC, "gen1")
    assert counter.host[-1].value == 5


def test_jaxs_records_name_the_function():
    counter = CompileCounter()

    def halve_and_tanh(x):
        return jnp.tanh(x / 2)

    jax.jit(halve_and_tanh)(jnp.ones((3,))).block_until_ready()
    named = {(r.kind, r.name) for r in counter.host}
    assert {("jax.trace", "halve_and_tanh"),
            ("jax.lower", "jit(halve_and_tanh)"),
            ("jax.request", "jit(halve_and_tanh)")} <= named
    # the same intervals as the stage records, on the same clock
    assert [r.end_ns for r in counter.host if r.kind.startswith("jax.")] == [
        at for _, _, at in counter.records]
    assert counter.requests()[-1][::3] == ("jit(halve_and_tanh)", False)


_CHILD_NAMED = r"""
import json
import jax, jax.numpy as jnp
from kungfu_tpu.utils.compile_cache import (CompileCounter,
                                            enable_compile_cache)
enable_compile_cache()
counter = CompileCounter()
def scaled_tanh(x):
    return jnp.tanh(x) * 3
jax.jit(scaled_tanh)(jnp.ones((8, 8))).block_until_ready()
print(json.dumps([[name, hit] for name, _, _, hit in counter.requests()]))
"""


def test_a_program_from_the_cache_is_told_from_one_compiled(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    cold, warm = _child(env, _CHILD_NAMED), _child(env, _CHILD_NAMED)
    assert ["jit(scaled_tanh)", False] in cold
    assert not any(hit for _, hit in cold)
    assert ["jit(scaled_tanh)", True] in warm


def test_records_are_mirrored_into_kftrace_while_armed(no_automatic_gc):
    from kungfu_tpu import trace as kftrace
    rec = kftrace.arm(capacity=1000)
    try:
        counter = CompileCounter()
        counter.add(counter.HANDOUT, "", 1_000, 3_000, 7, 2)
        gc.collect()
        # a collection inside kftrace's own frames must not wait for its
        # lock: its record waits for the next one instead
        with rec._lock:
            cc._on_gc("start", OLDER)
            cc._on_gc("stop", OLDER)
        assert [e["name"] for e in rec.tail(2)] == ["feed.handout",
                                                    "gc gen2"]
        counter.add(counter.STAGE, "", 4_000, 9_000, 8)
        tail = rec.tail(4)
    finally:
        kftrace.disarm()
    assert [(e["name"], e["cat"]) for e in tail] == [
        ("feed.handout", "host.feed"), ("gc gen2", "host.gc"),
        ("gc gen1", "host.gc"), ("feed.stage", "host.feed")]
    assert tail[0]["ts"] == pytest.approx(1e-6)
    assert tail[0]["dur"] == pytest.approx(2e-6)
    assert tail[0]["attrs"] == {"seq": 7, "depth": 2}
    assert tail[2]["attrs"] == {"collected": 5}


def test_records_from_many_threads_are_all_kept(no_automatic_gc):
    """Threads adding records and setting off collections at once: no
    record is lost and no collection's start is taken for another's."""
    counter = CompileCounter()
    n_threads, each = 2 * (os.cpu_count() or 4), 10
    before = len(_collections(counter))
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)

    def work(t):
        for i in range(each):
            counter.add(counter.STAGE, "", i, i + 1, t * each + i)
            gc.collect()

    try:
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(switch)
    staged = sorted(r.seq for r in counter.host if r.kind == counter.STAGE)
    assert staged == list(range(n_threads * each))
    pauses = _collections(counter)[before:]
    assert 0 < len(pauses) <= n_threads * each
    for a, b in zip(pauses, pauses[1:]):
        assert a.start_ns <= a.end_ns <= b.start_ns


def test_records_are_laid_on_a_profilers_clock(no_automatic_gc):
    counter = CompileCounter()
    counter.add(counter.STAGE, "", 4_000, 4_500, 3)
    counter.add(counter.GC, "gen2", 5_000, 7_000)
    assert counter.on_profile_clock(offset_ns=100, profile_start_ns=1_000,
                                    since_ns=4_500) == [("gc gen2", 4_100,
                                                         2_000)]
