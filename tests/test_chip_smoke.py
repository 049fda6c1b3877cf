"""chip_smoke.py off the chip: ``--tiny`` rehearses every phase on the
CPU, and nothing but that flag accepts a CPU.

The full-width run happens on the chip (README "Running on the chip");
here the same phase code runs at toy widths on the suite's eight
virtual devices, so the elastic phase runs too.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _run(*args, env=None, timeout=600):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=timeout, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))


@pytest.fixture(scope="module")
def tiny_run():
    r = _run("--tiny")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    return r


def test_tiny_passes_on_cpu_and_says_so(tiny_run):
    lines = tiny_run.stdout.strip().splitlines()
    device = {"platform": "cpu", "kind": "cpu", "count": 8}
    # the LAST line is the result, with exactly these keys ...
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    # ... and the line above it is the summary
    doc = json.loads(lines[-2])
    assert doc["ok"] is True
    assert doc["tiny"] is True
    assert '"tiny": true' in lines[-2] and '"platform": "cpu"' in lines[-2]
    assert doc["device"] == device
    assert list(doc)[-1] == "claim" and doc["claim"] is None
    assert lines[0].startswith("chip_smoke: platform=cpu")


def test_tiny_runs_every_phase(tiny_run):
    doc = json.loads(tiny_run.stdout.strip().splitlines()[-2])
    assert list(doc["phases"]) == ["train-resnet", "train-gpt", "kernels",
                                   "serve", "elastic"]
    assert all(p["ok"] is True for p in doc["phases"].values())
    # one line per phase, before the JSON
    for name in doc["phases"]:
        assert f"chip_smoke: phase {name}: ok" in tiny_run.stdout
    gpt = doc["phases"]["train-gpt"]
    assert gpt["attn"] == "flash" and gpt["losses"][-1] < gpt["losses"][0]
    serve = doc["phases"]["serve"]
    assert serve["attend"] == "fused" and serve["f32_equals_generate"] >= 3
    elastic = doc["phases"]["elastic"]
    assert elastic["schedule"] == [8, 4, 8]
    assert elastic["lanes_bit_identical"] is True


def test_without_tiny_a_cpu_is_refused_before_any_phase():
    r = _run()
    assert r.returncode not in (0, None)
    assert r.stdout.strip() == ""                 # no result, no phase
    assert "No phase ran" in r.stderr


def test_one_device_reports_elastic_not_run(monkeypatch):
    """On one device the elastic phase is reported, never silently
    skipped (in-process: the suite's devices, one visible)."""
    import jax
    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    assert chip_smoke.phase_elastic(chip_smoke.TINY) == {
        "not_run": "device_count=1"}


def test_a_raising_phase_fails_the_run(monkeypatch, capsys):
    calls = []

    def boom(sz):
        raise RuntimeError("kernel refused")

    def fine(sz):
        calls.append(sz.tiny)
        return {"note": "ran after the failure"}

    monkeypatch.setattr(chip_smoke, "PHASES",
                        [("boom", boom), ("fine", fine)])
    rc = chip_smoke.main(["--tiny"])
    out = capsys.readouterr().out.strip().splitlines()
    doc = json.loads(out[-2])
    assert rc != 0 and doc["ok"] is False
    assert json.loads(out[-1]) == {"ok": False, "device": doc["device"]}
    assert doc["phases"]["boom"]["ok"] is False
    assert "kernel refused" in doc["phases"]["boom"]["error"]
    # later phases still run, so one run shows every failure
    assert calls == [True] and doc["phases"]["fine"]["ok"] is True


def test_mosaic_is_required_on_a_tpu_only(monkeypatch):
    """A lowered module without the Mosaic call fails the check exactly
    when the backend is a TPU (interpret mode must not pass there)."""
    assert chip_smoke._require_mosaic("module {}", "x") == 0
    monkeypatch.setattr(chip_smoke, "_on_tpu", lambda: True)
    with pytest.raises(AssertionError, match="Mosaic"):
        chip_smoke._require_mosaic("module {}", "x")
    text = "a tpu_custom_call b tpu_custom_call"
    assert chip_smoke._require_mosaic(text, "x", at_least=2) == 2


def test_full_sizes_are_the_preset_widths():
    """Only batch, steps and serving depth are cut — never a width."""
    from kungfu_tpu.benchmarks.gpt import PRESETS, parse_args
    a = parse_args(PRESETS["470m"])
    g = chip_smoke.FULL.gpt
    assert (g["d_model"], g["n_layers"], g["n_heads"], g["n_kv_heads"],
            g["d_ff"], g["vocab_size"], g["max_seq"]) == (
        a.d_model, a.n_layers, a.n_heads, a.n_kv_heads, a.d_ff, a.vocab,
        a.seq)
    assert g["rope"] and g["mlp"] == "swiglu"
    assert chip_smoke.FULL.ce_chunk == a.chunked_ce
    assert chip_smoke.FULL.resnet_stages is None          # ResNet-50
    assert (chip_smoke.FULL.image, chip_smoke.FULL.resnet_batch) == (224, 256)
    assert {c[-1] for c in chip_smoke.FULL.flash_cases} == {64, 128}
    assert {c[1] for c in chip_smoke.FULL.flash_cases} == {2048, 8192}
    assert {c[3] for c in chip_smoke.FULL.paged_cases} == {64, 128}


def test_importing_package_and_launcher_initialises_no_backend():
    """A parent that has touched jax holds the chip; the launcher and
    every orchestrating script import the package before they spawn the
    process that needs it."""
    code = ("import kungfu_tpu, kungfu_tpu.launcher, kungfu_tpu.utils."
            "compile_cache; from jax._src import xla_bridge; "
            "print(xla_bridge.backends_are_initialized())")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == "False"


def test_bench_needs_a_tpu_and_prints_no_metric():
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       cwd=REPO, capture_output=True, text=True,
                       timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr
    src = open(os.path.join(REPO, "bench.py")).read()
    assert "subprocess" not in src and "ResNet(" not in src
