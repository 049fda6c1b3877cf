"""`python -m kungfu_tpu.serving` — the serving binary, end to end.

A subprocess serves a tiny model over HTTP; the test drives /generate
against it and checks the tokens against an in-process oracle built
from the same seed (and, for the --npz path, from saved weights).
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kungfu_tpu.checkpoint import save_npz
from kungfu_tpu.models import gpt as G

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG_FLAGS = ["--vocab", "61", "--d-model", "16", "--n-heads", "4",
             "--n-layers", "2", "--d-ff", "32", "--max-seq", "64",
             "--slots", "2", "--block", "4", "--blocks", "32",
             "--chunk", "2", "--buckets", "8,16", "--port", "0",
             "--dtype", "float32"]
CFG = G.GPTConfig(vocab_size=61, d_model=16, n_heads=4, n_layers=2,
                  d_ff=32, max_seq=64, dtype=jnp.float32)


def _start(extra, tmp_err, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    err_f = open(tmp_err, "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "kungfu_tpu.serving"] + CFG_FLAGS + extra,
        stdout=subprocess.PIPE, stderr=err_f, text=True,
        cwd=REPO, env=env)
    # readline() blocks, so the startup deadline needs teeth of its own:
    # a watchdog kill turns a silent wedge into EOF + a failed assert
    # with the captured stderr as diagnostics
    watchdog = threading.Timer(timeout, proc.kill)
    watchdog.start()
    try:
        while True:
            line = proc.stdout.readline()
            if line.startswith("SERVING ready on "):
                host, port = line.strip().rsplit(" ", 1)[-1].split(":")
                return proc, host, int(port)
            if not line or proc.poll() is not None:
                proc.kill()
                err_f.flush()
                tail = open(tmp_err).read()[-1500:]
                raise AssertionError(
                    f"server did not come up: {line!r}\n{tail}")
    finally:
        watchdog.cancel()


def _post(host, port, payload):
    req = urllib.request.Request(
        f"http://{host}:{port}/generate",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


def _stop(proc):
    proc.send_signal(signal.SIGTERM)
    try:
        proc.wait(timeout=30)
    except subprocess.TimeoutExpired:
        proc.kill()


def _oracle(params, prompt, n_new):
    out = G.generate(params, CFG, jnp.asarray([prompt], jnp.int32), n_new)
    return np.asarray(out)[0].tolist()


def test_cli_serves_seeded_model(tmp_path):
    proc, host, port = _start(["--seed", "3"], str(tmp_path / "err.log"))
    try:
        params = G.init_params(jax.random.PRNGKey(3), CFG)
        prompt = [4, 9, 2, 7]
        r = _post(host, port, {"prompt": prompt, "max_new": 5})
        assert r["tokens"] == _oracle(params, prompt, 5)
    finally:
        _stop(proc)
    assert proc.returncode == 0      # clean SIGTERM shutdown


def test_cli_serves_npz_weights(tmp_path):
    params = G.init_params(jax.random.PRNGKey(11), CFG)
    path = str(tmp_path / "w.npz")
    save_npz(path, params)
    # different --seed proves the npz weights (not the seed) are served
    proc, host, port = _start(["--seed", "0", "--npz", path],
                              str(tmp_path / "err.log"))
    try:
        prompt = [1, 2, 3]
        r = _post(host, port, {"prompt": prompt, "max_new": 6})
        assert r["tokens"] == _oracle(params, prompt, 6)
    finally:
        _stop(proc)


def test_cli_kv_int8_and_tp(tmp_path):
    """--kv-int8 --tp 2 serve the same model (int8 cache + tensor
    parallelism through the binary); greedy tokens must still come from
    the served weights (int8 noise can flip near-ties on random weights,
    so assert the shape/validity and determinism across two calls)."""
    # vocab overridden to a tp-divisible size (last --vocab flag wins)
    proc, host, port = _start(["--seed", "3", "--kv-int8", "--tp", "2",
                               "--vocab", "64"],
                              str(tmp_path / "err.log"))
    try:
        prompt = [4, 9, 2, 7]
        a = _post(host, port, {"prompt": prompt, "max_new": 5})
        b = _post(host, port, {"prompt": prompt, "max_new": 5})
        assert len(a["tokens"]) == 5 and a["tokens"] == b["tokens"]
        assert all(0 <= t < 64 for t in a["tokens"])
    finally:
        _stop(proc)
    assert proc.returncode == 0


def test_cli_weights_int8(tmp_path):
    """--weights-int8 through the binary: valid deterministic tokens
    from the quantized weights (same near-tie caveat as kv-int8)."""
    proc, host, port = _start(["--seed", "4", "--weights-int8"],
                              str(tmp_path / "err.log"))
    try:
        prompt = [3, 8, 1, 6]
        a = _post(host, port, {"prompt": prompt, "max_new": 5})
        b = _post(host, port, {"prompt": prompt, "max_new": 5})
        assert len(a["tokens"]) == 5 and a["tokens"] == b["tokens"]
    finally:
        _stop(proc)
    assert proc.returncode == 0


def test_cli_rejects_bad_npz(tmp_path):
    bad = G.GPTConfig(vocab_size=61, d_model=8, n_heads=2, n_layers=1,
                      d_ff=16, max_seq=64, dtype=jnp.float32)
    path = str(tmp_path / "bad.npz")
    save_npz(path, G.init_params(jax.random.PRNGKey(0), bad))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "kungfu_tpu.serving"] + CFG_FLAGS
        + ["--npz", path], capture_output=True, text=True, timeout=120,
        cwd=REPO, env=env)
    assert proc.returncode != 0
    assert "shape" in proc.stderr or "missing" in proc.stderr