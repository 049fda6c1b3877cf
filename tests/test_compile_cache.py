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

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Restore the jax cache options a test changed."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
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
                  "compiled": counter.compiled, "hits": counter.cache_hits}))
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


def test_no_knob_and_no_home_directory_default():
    """The package's own cache knob is gone (one place decides, from
    outside), and no code names the old home-directory default."""
    from kungfu_tpu.utils import knobs
    assert not [k for k in knobs.KNOBS if "COMPILE_CACHE" in k]
    src = open(cc.__file__).read()
    assert "expanduser" not in src and "knobs" not in src
