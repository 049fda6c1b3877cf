"""Where the persistent XLA compile cache lives (utils/compile_cache.py).

``JAX_COMPILATION_CACHE_DIR`` wins and then the code sets no directory;
without it an accelerator gets ``<checkout>/.jax_cache`` — a path made
from the package's location, the same in every process — and the CPU
gets no cache at all.
"""
import json
import os
import subprocess
import sys

import jax
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


def _child(env):
    r = subprocess.run([sys.executable, "-c", _CHILD], env=env, cwd=REPO,
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
