"""Synthetic allreduce benchmark CLI (reference: v1/benchmarks/__main__.py)."""
import math
import subprocess
import sys

import pytest

from kungfu_tpu.benchmarks import show_rate, show_size
from kungfu_tpu.benchmarks.__main__ import main as bench_main


def test_show_size_units():
    assert show_size(100) == "100"
    assert show_size(2048) == "2.00Ki"
    assert show_size(3 * 1024 * 1024) == "3.00Mi"
    assert show_size(5 * 1024 ** 3) == "5.00Gi"


def test_show_rate_units():
    assert show_rate(1024 ** 2, 1.0) == "1.00MiB/s"
    assert show_rate(10, 1.0) == "10.00B/s"


def test_xla_bench_emits_result_line(capsys):
    bench_main(["--model", "SLP", "--method", "XLA",
                "--steps", "2", "--warmup-steps", "1"])
    out = capsys.readouterr().out
    assert "RESULT: " in out
    assert '"method":"XLA"' in out
    assert '"np":' in out


def test_hier_bench_fused(capsys):
    bench_main(["--model", "SLP", "--method", "HIER", "--hosts", "2",
                "--devices", "4", "--fuse",
                "--steps", "1", "--warmup-steps", "0"])
    out = capsys.readouterr().out
    assert "RESULT: " in out and '"fuse":true' in out


def test_max_count_truncates(capsys):
    bench_main(["--model", "ResNet50", "--method", "XLA", "--max-count", "3",
                "--steps", "1", "--warmup-steps", "0"])
    out = capsys.readouterr().out
    assert "all reduce 3 tensors" in out


def test_native_bench_via_launcher():
    from kungfu_tpu import native
    if not native.available():
        pytest.skip("native lib unavailable")
    cmd = [sys.executable, "-m", "kungfu_tpu.launcher", "-q", "-np", "2",
           sys.executable, "-m", "kungfu_tpu.benchmarks", "--model", "SLP",
           "--method", "NATIVE", "--steps", "1", "--warmup-steps", "0"]
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=180)
    assert out.returncode == 0, out.stderr
    assert "RESULT: " in out.stdout


def test_gpt_bench_emits_json(capsys):
    import json

    from kungfu_tpu.benchmarks.gpt import main as gpt_main

    rc = gpt_main(["--d-model", "32", "--n-layers", "1", "--n-heads", "2",
                   "--d-ff", "64", "--vocab", "128", "--seq", "32",
                   "--batch", "2", "--steps", "2", "--warmup-steps", "1",
                   "--rope", "--swiglu"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    d = json.loads(out)
    assert d["metric"] == "gpt_tokens_per_sec_per_chip"
    assert d["value"] > 0
    assert d["params"] > 0


def test_gpt_decode_bench_emits_json(capsys):
    import json

    from kungfu_tpu.benchmarks.gpt import main as gpt_main

    rc = gpt_main(["--decode", "--d-model", "32", "--n-layers", "1",
                   "--n-heads", "2", "--d-ff", "64", "--vocab", "128",
                   "--seq", "32", "--prompt-len", "8", "--batch", "2",
                   "--steps", "2"])
    assert rc == 0
    d = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert d["metric"] == "gpt_decode_tokens_per_sec_per_chip"
    assert d["value"] > 0
    assert d["new_tokens"] == 24


def test_gpt_bench_chunked_ce(capsys):
    import json

    from kungfu_tpu.benchmarks.gpt import main as gpt_main

    rc = gpt_main(["--d-model", "32", "--n-layers", "1", "--n-heads", "2",
                   "--d-ff", "64", "--vocab", "128", "--seq", "32",
                   "--batch", "2", "--steps", "2", "--warmup-steps", "1",
                   "--chunked-ce", "64"])
    assert rc == 0
    d = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert d["metric"] == "gpt_tokens_per_sec_per_chip"
    assert math.isfinite(d["loss"])


def test_gpt_bench_decode_rejects_training_flags():
    from kungfu_tpu.benchmarks.gpt import main as gpt_main

    with pytest.raises(SystemExit, match="training"):
        gpt_main(["--decode", "--chunked-ce", "64", "--d-model", "32",
                  "--n-heads", "2", "--n-layers", "1", "--vocab", "64",
                  "--seq", "32"])


def test_gpt_preset_expansion_and_override():
    """--preset splices the README row's flags; explicit flags win; both
    --preset X and --preset=X forms parse; bad names are rejected."""
    from kungfu_tpu.benchmarks.gpt import PRESETS, parse_args

    a = parse_args(["--preset", "470m"])
    assert (a.d_model, a.n_layers, a.accum, a.chunked_ce) == \
        (1024, 24, 32, 16384)
    assert a.rope and a.swiglu

    b = parse_args(["--preset=164m"])
    assert (b.d_model, b.batch, b.accum) == (768, 64, 16)

    # explicit flag overrides the preset value
    c = parse_args(["--preset", "470m", "--accum", "8"])
    assert c.accum == 8 and c.d_model == 1024

    with pytest.raises(SystemExit):
        parse_args(["--preset", "bogus"])
    assert set(PRESETS) == {"164m", "470m", "164m-long", "164m-hd128",
                            "164m-long-hd128", "470m-hd128"}
    # the high-MFU rows: same d_model/params, MXU-filling 128-wide heads
    d = parse_args(["--preset", "470m-hd128"])
    assert (d.d_model, d.n_heads, d.n_kv_heads) == (1024, 8, 2)
    e = parse_args(["--preset", "164m-long-hd128"])
    assert (e.d_model, e.n_heads, e.seq) == (768, 6, 8192)


def test_roofline_harness_produces_artifact(tmp_path):
    """The kernel-roofline harness (VERDICT r2: the platform-ceiling
    claim needs a reproducible artifact) runs end to end and writes the
    JSON schema the README cites."""
    import json
    import os
    out = tmp_path / "roofline.json"
    r = subprocess.run(
        [sys.executable, "-m", "kungfu_tpu.benchmarks.roofline",
         "--tiny", "--out", str(out)],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-800:]
    doc = json.loads(out.read_text())
    ops = {x["op"].split("_")[0] for x in doc["results"]}
    assert {"matmul", "flash", "hbm"} <= ops
    timed = [x for x in doc["results"] if "seconds" in x]
    assert all(x["seconds"] > 0 for x in timed)
