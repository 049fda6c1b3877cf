"""The manifest is sound, the harness finds new cells and metrics as files,
and a run refuses where it cannot measure."""
import hashlib
import json
import os
import subprocess
import sys

import pytest

from perf_testdata import ROOT, copy_data
from perf import peaks
from perf.manifest import NAME, UNIT, Manifest


def test_manifest_is_sound():
    assert Manifest(ROOT).problems() == []


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_and_units_use_allowed_characters(kind):
    for entry in Manifest(ROOT).data[kind]:
        assert NAME.match(entry["name"]), entry["name"]
        if "unit" in entry:
            assert UNIT.match(entry["unit"]), entry["unit"]
        for text in ("why", "layer", "source"):
            if text in entry:
                assert 1 <= len(entry[text]) <= 200 and "\n" not in entry[text]


def test_manifest_keys_are_the_contracts():
    data = Manifest(ROOT).data
    assert set(data) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    for m in data["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in data["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    for w in data["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for c in data["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in data["paths"])


def test_every_cell_reports_what_its_metrics_move():
    m = Manifest(ROOT)
    for cell in m.cells:
        e2e = {x["name"] for x in m.metrics("end_to_end", cell)}
        assert "setup_s" in e2e and len(e2e) >= 2
        for x in m.metrics("per_layer", cell):
            assert x["moves"] in e2e, (cell, x["name"])


def test_a_problem_is_found(tmp_path):
    copy_data(ROOT, str(tmp_path))
    path = tmp_path / "BENCHMARK.json"
    data = json.loads(path.read_text())
    data["per_layer"][0]["moves"] = "images_per_s"      # a gpt metric
    data["workloads"][0]["traffic"] = "nowhere"
    path.write_text(json.dumps(data))
    found = "\n".join(Manifest(str(tmp_path)).problems())
    assert "does not report images_per_s" in found
    assert "no perf/traffic/nowhere.json" in found


def _digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "perf")):
        for f in files:
            p = os.path.join(d, f)
            out[p] = hashlib.sha256(open(p, "rb").read()).hexdigest()
    return out


def test_a_later_pr_adds_a_cell_and_a_metric_as_files_only(tmp_path):
    """A new traffic mix, its limits, a new metric and a new reader are
    dropped in beside the others; the harness lists the cell and finds the
    reader, and no file that was there is edited."""
    root = str(tmp_path)
    copy_data(ROOT, root)
    before = _digests(root)
    perf = tmp_path / "perf"
    traffic = json.loads((perf / "traffic" / "train-4k.json").read_text())
    traffic.update(batch=2, accum_steps=1)
    (perf / "traffic" / "train-8k-tokens.json").write_text(
        json.dumps(traffic))
    (perf / "limits" / "mistral7b-train-8k.json").write_text(
        (perf / "limits" / "mistral7b-train-4k.json").read_text())
    (perf / "metrics" / "loss_fetch_ms.gpt.json").write_text(json.dumps(
        {"reader": "span_mean_ms", "args": {"span": "loss_fetch"}}))
    manifest = json.loads((tmp_path / "BENCHMARK.json").read_text())
    manifest["workloads"].append({
        "name": "mistral7b-train-8k", "config": "mistral7b-train",
        "traffic": "train-8k-tokens", "chips": 1, "why": "half the batch"})
    manifest["end_to_end"][0]["workloads"].append("mistral7b-train-8k")
    manifest["per_layer"].append({
        "name": "loss_fetch_ms.gpt", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "whole step",
        "moves": "tokens_per_s", "workloads": ["mistral7b-train-8k"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    m = Manifest(root)
    assert m.problems() == []
    cell = m.cell("mistral7b-train-8k")
    assert cell["traffic"]["batch"] == 2
    assert cell["config"]["hidden_size"] == 4096
    names = [x["name"] for x in m.metrics("per_layer", "mistral7b-train-8k")]
    assert names == ["loss_fetch_ms.gpt"]
    read, kw = m.reader("loss_fetch_ms.gpt")
    assert callable(read) and kw == {"span": "loss_fetch"}
    assert "loss_fetch_ms.gpt" not in [
        x["name"] for x in m.metrics("per_layer", "mistral7b-train-4k")]
    after = _digests(root)
    assert all(after[p] == h for p, h in before.items())


def test_peaks_table_names_its_source():
    row = peaks.peaks_for("TPU v5 lite")
    assert row["flops_bf16"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    assert "v5e" in row["source"]


def test_a_device_not_in_the_table_is_an_error():
    with pytest.raises(peaks.UnknownDevice, match="TPU v9"):
        peaks.peaks_for("TPU v9")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")


def test_a_chips_peak_is_its_arrays_and_its_programs_temporaries():
    """`memory_stats()` of the ResNet cell after five steps (my chip run,
    PR 25): the step's 9.1 GB of temporaries are under `reserved`."""
    from perf.run import device_peak_bytes
    stats = {"bytes_in_use": 513634304, "peak_bytes_in_use": 1155037696,
             "bytes_reserved": 9082535936, "peak_bytes_reserved": 9082535936,
             "bytes_limit": 16909336064}
    assert device_peak_bytes(stats) == 1155037696 + 9082535936
    assert device_peak_bytes({"peak_bytes_in_use": 7}) == 7
    assert device_peak_bytes({}) == 0


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "perf/run.py", "--workload", "mistral7b-train-4k",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_is_an_error_and_prints_no_result():
    done = _run(ROOT)
    assert done.returncode != 0
    assert done.stdout == ""
    assert "TPU" in done.stderr


def test_without_the_program_there_is_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths`, the command exits non-zero and prints nothing."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in Manifest(ROOT).data["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p)
    done = _run(str(tmp_path), {"PYTHONPATH": ""})
    assert done.returncode != 0
    assert done.stdout == ""
