"""kfprof: device-time attribution plane (monitor/profiler.py).

Covers the three tiers data-plane-free: the StepPhases breakdown
arithmetic and its published series, the guarded capture path
(utils/trace.py + the /profile endpoint + the cluster fan-out), the
roofline gauges, the cluster-meta phase shares, and the kfdoctor
``perf`` detector — including the chaos ``slow-compute-doctor``
acceptance twin: an injected dominant phase must be named by the
Finding's kind, and the clean / low-but-steady twins must stay silent
(the CPU false-positive guard).
"""
import json
import os
import threading
import urllib.request

import pytest

from kungfu_tpu.monitor import (MONITOR_PORT_OFFSET, MetricsServer,
                                Monitor)
from kungfu_tpu.monitor import cluster as kcluster
from kungfu_tpu.monitor import profiler as prof
from kungfu_tpu.monitor.doctor import Doctor, detect_perf
from kungfu_tpu.monitor.history import MetricsHistory


# --------------------------------------------------------- step phases
def test_step_phases_host_is_remainder():
    mon = Monitor()
    sp = prof.StepPhases(loop="train", monitor=mon)
    sp.add("compute", 0.5)
    sp.add("collective", 0.2)
    sp.add("transfer", 0.1)
    out = sp.publish(1.0, rank=0, step=3)
    assert out["compute"] == pytest.approx(0.5)
    assert out["collective"] == pytest.approx(0.2)
    assert out["transfer"] == pytest.approx(0.1)
    assert out["host"] == pytest.approx(0.2)
    assert sum(out.values()) == pytest.approx(1.0)
    text = mon.render_metrics()
    assert 'phase="compute"' in text and 'phase="host"' in text
    assert 'loop="train"' in text
    assert "kungfu_tpu_step_phase_seconds_sum" in text


def test_step_phases_host_never_negative():
    """Over-attribution (timer overlap) must clamp host at 0, not go
    negative — the shares stay a probability distribution."""
    sp = prof.StepPhases(monitor=Monitor())
    sp.add("compute", 2.0)
    out = sp.publish(1.0)
    assert out["host"] == 0.0


def test_step_phases_resets_between_steps():
    sp = prof.StepPhases(monitor=Monitor())
    sp.add("compute", 0.4)
    first = sp.publish(0.5)
    second = sp.publish(0.5)      # nothing accumulated since
    assert first["compute"] == pytest.approx(0.4)
    assert second["compute"] == 0.0
    assert second["host"] == pytest.approx(0.5)


def test_step_phases_rejects_unknown_and_derived_phase():
    sp = prof.StepPhases(monitor=Monitor())
    with pytest.raises(ValueError):
        sp.add("gpu", 0.1)
    with pytest.raises(ValueError):
        sp.add("host", 0.1)       # host is derived, never added


def test_last_attribution_tracks_both_loops():
    mon = Monitor()
    prof.StepPhases(loop="train", monitor=mon).publish(0.2)
    prof.StepPhases(loop="serve", monitor=mon).publish(0.1)
    att = prof.last_attribution()
    assert "train" in att["phases"] and "serve" in att["phases"]


# ------------------------------------------------------------- capture
def test_capture_idempotent_and_counted(tmp_path):
    """Satellite 1: double-start answers None (busy) instead of raising
    out of jax.profiler, the failure is counted on the monitor, and a
    double stop is a no-op."""
    from kungfu_tpu.monitor import get_monitor
    from kungfu_tpu.utils import trace as utrace

    def failures():
        text = get_monitor().render_metrics()
        return sum(
            float(line.rsplit(" ", 1)[1])
            for line in text.splitlines()
            if line.startswith("kungfu_tpu_profile_failures_total"))

    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    assert utrace.stop_capture() is None          # nothing running: no-op
    before = failures()
    assert utrace.start_capture(d1) == d1
    try:
        assert utrace.capturing() == d1
        assert utrace.start_capture(d2) is None   # busy, not RuntimeError
        assert failures() == before + 1
    finally:
        assert utrace.stop_capture() == d1
    assert utrace.capturing() is None
    assert utrace.stop_capture() is None          # idempotent


def test_capture_takes_device_events_only(tmp_path, monkeypatch):
    """An operator's /profile gets the trace the benchmark gets: with the
    host tracer on, 30 ResNet-50 steps wrote 0.94 GB (PERF.md)."""
    import jax
    from kungfu_tpu.utils import trace as utrace
    seen = {}
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda logdir, **kw: seen.update(logdir=logdir, **kw))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    logdir = str(tmp_path / "t")
    assert utrace.start_capture(logdir) == logdir
    assert utrace.stop_capture() == logdir
    options = seen["profiler_options"]
    assert seen["logdir"] == logdir
    assert (options.host_tracer_level, options.python_tracer_level) == (0, 0)


def test_capture_context_does_not_stop_foreign_capture(tmp_path):
    from kungfu_tpu.utils import trace as utrace
    own = str(tmp_path / "own")
    assert utrace.start_capture(own) == own
    try:
        with utrace.capture(str(tmp_path / "nested")) as got:
            assert got is None                    # busy: no logdir
        # the nested block must NOT have stopped the outer capture
        assert utrace.capturing() == own
    finally:
        assert utrace.stop_capture() == own


def test_profile_endpoint_roundtrip():
    """/profile on the worker MetricsServer answers 200 JSON with the
    capture's artifact paths and the attribution snapshot."""
    import jax
    import jax.numpy as jnp
    mon = Monitor()
    srv = MetricsServer(mon).start()
    fn = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64), jnp.float32)
    stop = threading.Event()

    def churn():
        while not stop.is_set():
            fn(x).block_until_ready()

    t = threading.Thread(target=churn, daemon=True)
    t.start()
    try:
        raw = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/profile?duration_s=0.2",
            timeout=30).read()
    finally:
        stop.set()
        t.join(timeout=5)
        srv.stop()
    doc = json.loads(raw)
    assert doc["ok"], doc
    assert doc["artifacts"], "capture produced no artifacts"
    assert any(a.endswith("kfprof_meta.json") for a in doc["artifacts"])
    assert "attribution" in doc


def test_profile_endpoint_busy_answers_json(tmp_path):
    """A busy profiler is an answer (ok=false), never a 500 — the
    cluster fan-out must see the reason, not an HTTPError."""
    from kungfu_tpu.utils import trace as utrace
    own = str(tmp_path / "own")
    assert utrace.start_capture(own) == own
    try:
        doc = prof.handle_profile_request("/profile?duration_s=0.1")
        assert doc["ok"] is False
        assert "error" in doc
    finally:
        assert utrace.stop_capture() == own


def test_profile_duration_parse_clamps():
    assert prof._parse_duration("/profile?duration_s=3") == 3.0
    assert prof._parse_duration("/profile") == 2.0
    assert prof._parse_duration("/profile?duration_s=junk") == 2.0
    assert prof._parse_duration("/profile?duration_s=9999") == 120.0
    assert prof._parse_duration("/profile?duration_s=-4") == 0.05


def test_profile_cluster_merges_dead_target():
    """Fan-out discipline: one live worker + one dead port must yield a
    merged doc with the live capture's artifacts and ok=False overall
    (the dead worker's error is IN the answer, not an exception)."""
    import jax
    import jax.numpy as jnp
    from kungfu_tpu.utils import rpc as _rpc
    mon = Monitor()
    srv = MetricsServer(mon).start()
    fn = jax.jit(lambda x: x @ x)
    x = jnp.ones((32, 32), jnp.float32)
    stop = threading.Event()

    def churn():
        while not stop.is_set():
            fn(x).block_until_ready()

    t = threading.Thread(target=churn, daemon=True)
    t.start()
    live = ("127.0.0.1", srv.port - MONITOR_PORT_OFFSET)
    # a port nothing listens on (the server's own +1 is as good as any)
    dead = ("127.0.0.1", srv.port - MONITOR_PORT_OFFSET + 1)
    try:
        doc = prof.profile_cluster([live, dead], 0.2,
                                   attempt_margin_s=3.0)
    finally:
        stop.set()
        t.join(timeout=5)
        srv.stop()
        _rpc.reset(f"http://{dead[0]}:{dead[1] + MONITOR_PORT_OFFSET}/")
    assert doc["ok"] is False                 # one worker failed
    workers = doc["workers"]
    assert workers[f"{live[0]}:{live[1]}"]["ok"] is True
    assert workers[f"{dead[0]}:{dead[1]}"]["ok"] is False
    assert doc["artifacts"], "live worker's artifacts must be merged"


# ------------------------------------------------------------ roofline
def test_load_ceilings_and_negative_cache(tmp_path):
    path = str(tmp_path / "ROOFLINE.json")
    with open(path, "w") as f:
        json.dump({"results": [
            {"op": "matmul_4096x4096x4096_bf16", "tflops": 169.43},
            {"op": "matmul_small", "tflops": 10.0},
            {"op": "hbm_copy_512MiB", "gib_per_s": 546.3}]}, f)
    ceil = prof.load_ceilings(path)
    assert ceil is not None
    assert ceil.matmul_flops == pytest.approx(169.43e12)
    assert ceil.hbm_bytes_s == pytest.approx(546.3 * 2 ** 30)
    missing = str(tmp_path / "nope.json")
    assert prof.load_ceilings(missing) is None
    assert prof.load_ceilings(missing) is None    # negative-cached


def test_publish_roofline_fractions():
    mon = Monitor()
    # a program costing 1e9 flops / 1e8 bytes, run in 10ms
    prof.publish_compiled_cost(_FakeCosted(1e9, 1e8), monitor=mon)
    ceil = prof.Ceilings(matmul_flops=1e12, hbm_bytes_s=1e11)
    out = prof.publish_roofline(0.010, monitor=mon, ceilings=ceil)
    assert out["mxu"] == pytest.approx(0.1)       # 1e11 of 1e12
    assert out["hbm"] == pytest.approx(0.1)       # 1e10 of 1e11
    assert out["best"] == pytest.approx(0.1)
    assert 'kungfu_tpu_roofline_fraction{bound="best"}' \
        in mon.render_metrics()


def test_publish_roofline_none_without_ceilings_or_cost():
    mon = Monitor()
    assert prof.publish_roofline(
        0.01, monitor=mon,
        ceilings=prof.Ceilings(0.0, 0.0)) is None


class _FakeCosted:
    """An AOT-costable step double (lower().compile().cost_analysis())."""

    def __init__(self, flops, hbm):
        self._cost = {"flops": flops, "bytes accessed": hbm}

    def lower(self, *a, **k):
        return self

    def compile(self):
        return self

    def cost_analysis(self):
        return dict(self._cost)


def test_publish_compiled_cost_env_gate(monkeypatch):
    monkeypatch.setenv(prof.ENV_COST, "0")
    mon = Monitor()
    assert prof.publish_compiled_cost(
        _FakeCosted(1.0, 1.0), monitor=mon) is None
    assert "kungfu_tpu_step_flops" not in mon.render_metrics()


def test_publish_compiled_cost_failure_counted():
    """A step that cannot be AOT-lowered must count a failure and
    return None — never break the training loop."""

    class Unlowerable:
        def lower(self, *a, **k):
            raise TypeError("donated buffer mismatch")

    mon = Monitor()
    assert prof.publish_compiled_cost(Unlowerable(), monitor=mon) is None
    assert 'kungfu_tpu_profile_failures_total{op="cost"} 1' \
        in mon.render_metrics()


# ------------------------------------------------ cluster phase shares
def _phase_expo(compute, collective, transfer, host, *,
                roofline=None) -> str:
    lines = []
    for phase, v in (("compute", compute), ("collective", collective),
                     ("transfer", transfer), ("host", host)):
        lines.append(
            f'kungfu_tpu_step_phase_seconds{{loop="train",'
            f'phase="{phase}",quantile="0.5"}} {v}')
        lines.append(
            f'kungfu_tpu_step_phase_seconds_sum{{loop="train",'
            f'phase="{phase}"}} {v * 10}')
        lines.append(
            f'kungfu_tpu_step_phase_seconds_count{{loop="train",'
            f'phase="{phase}"}} 10')
    if roofline is not None:
        lines.append(
            f'kungfu_tpu_roofline_fraction{{bound="best"}} {roofline}')
    return "\n".join(lines) + "\n"


def test_cluster_phase_shares_parse():
    text = _phase_expo(0.6, 0.2, 0.1, 0.1)
    shares = kcluster.phase_shares(text)
    assert shares["compute"] == pytest.approx(0.6)
    assert sum(shares.values()) == pytest.approx(1.0)
    assert kcluster.phase_shares("kungfu_tpu_step_seconds_sum 1\n") == {}


def test_cluster_aggregate_includes_share_meta():
    """Satellite: /cluster_metrics carries each worker's pre-digested
    phase shares so kft-doctor --url renders attribution from one
    scrape."""
    mon = Monitor()
    sp = prof.StepPhases(loop="train", monitor=mon)
    sp.add("compute", 0.8)
    sp.publish(1.0)
    srv = MetricsServer(mon).start()
    try:
        text = kcluster.aggregate(
            [("127.0.0.1", srv.port - MONITOR_PORT_OFFSET)])
    finally:
        srv.stop()
    assert "# TYPE kungfu_tpu_step_phase_share gauge" in text
    assert 'kungfu_tpu_step_phase_share{instance=' in text
    assert 'phase="compute"' in text


# ------------------------------------------------- perf detector (doctor)
def _feed(hist, inst, *, roofline, shares=(0.7, 0.1, 0.1, 0.1)):
    c, l, t, h = shares
    for r in roofline:
        hist.observe_text(inst, _phase_expo(c, l, t, h, roofline=r))


def test_detect_perf_names_dominant_phase():
    """The slow-compute-doctor acceptance twin: a roofline collapse with
    compute dominating the phase split must raise a compute-bound
    Finding naming the instance and rank."""
    hist = MetricsHistory(window=32)
    _feed(hist, "h0:1", roofline=[0.5] * 5 + [0.01] * 3,
          shares=(0.7, 0.1, 0.1, 0.1))
    findings = detect_perf(hist, roofline=0.05, drop=2.0, min_windows=3,
                           ranks={"h0:1": 1}, version=7)
    assert len(findings) == 1
    f = findings[0]
    assert f.kind == "compute-bound"
    assert f.instance == "h0:1"
    assert f.rank == 1
    assert f.version == 7
    assert f.severity == "critical"           # 50x drop >> 2*drop
    assert f.evidence["share_compute"] == pytest.approx(0.7)
    assert f.evidence["roofline_fraction"] == pytest.approx(0.01)


def test_detect_perf_collective_and_input_bound_kinds():
    hist = MetricsHistory(window=32)
    _feed(hist, "h0:1", roofline=[0.5] * 5 + [0.01] * 3,
          shares=(0.1, 0.6, 0.2, 0.1))
    _feed(hist, "h1:2", roofline=[0.5] * 5 + [0.01] * 3,
          shares=(0.1, 0.1, 0.6, 0.2))
    kinds = {f.instance: f.kind for f in detect_perf(hist)}
    assert kinds == {"h0:1": "collective-bound", "h1:2": "input-bound"}


def test_detect_perf_clean_twin_silent():
    """No fault, healthy fraction: silence."""
    hist = MetricsHistory(window=32)
    _feed(hist, "h0:1", roofline=[0.5] * 8)
    assert detect_perf(hist) == []


def test_detect_perf_low_but_steady_silent():
    """The CPU guard: a fraction that was ALWAYS far below any
    TPU-calibrated threshold must not fire — only a drop against the
    run's own baseline is diagnosable (chaos clean-twin acceptance)."""
    hist = MetricsHistory(window=32)
    _feed(hist, "h0:1", roofline=[0.001] * 8)
    assert detect_perf(hist, roofline=0.05, drop=2.0) == []


def test_detect_perf_needs_baseline():
    """Fewer than 2x min_windows snapshots: no baseline, no finding."""
    hist = MetricsHistory(window=32)
    _feed(hist, "h0:1", roofline=[0.5, 0.01, 0.01, 0.01])
    assert detect_perf(hist, min_windows=3) == []


def test_detect_perf_serve_loop_fallback():
    """An inference-only worker publishes loop="serve" phases; the
    detector's loop fallback must still attribute."""
    hist = MetricsHistory(window=32)
    for r in [0.5] * 5 + [0.01] * 3:
        lines = []
        for phase, v in (("compute", 0.1), ("collective", 0.0),
                         ("transfer", 0.0), ("host", 0.5)):
            lines.append(
                f'kungfu_tpu_step_phase_seconds{{loop="serve",'
                f'phase="{phase}",quantile="0.5"}} {v}')
        lines.append(
            f'kungfu_tpu_roofline_fraction{{bound="best"}} {r}')
        hist.observe_text("s0:1", "\n".join(lines) + "\n")
    findings = detect_perf(hist)
    assert [f.kind for f in findings] == ["host-bound"]


def test_doctor_runs_perf_detector():
    """Doctor.diagnose wires detect_perf: the same collapse surfaces
    through the full diagnosis path with gauges exported."""
    mon = Monitor()
    doc = Doctor(window=32, monitor=mon)
    for r in [0.5] * 5 + [0.01] * 3:
        doc.observe("h0:1", _phase_expo(0.7, 0.1, 0.1, 0.1, roofline=r))
    findings = doc.diagnose(ranks={"h0:1": 2}, version=3)
    perf = [f for f in findings if f.kind.endswith("-bound")]
    assert len(perf) == 1 and perf[0].rank == 2
    assert 'kungfu_tpu_finding_active{kind="compute-bound",rank="2"} 1' \
        in mon.render_metrics()


# --------------------------------------------------------- report tool
def test_kfprof_report_records_and_bench_block(tmp_path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "kfprof_report",
        os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                     "kfprof_report.py"))
    rep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rep)
    text = (
        'kungfu_tpu_step_phase_seconds_sum{instance="w0:1",'
        'loop="train",phase="compute"} 8.0\n'
        'kungfu_tpu_step_phase_seconds_sum{instance="w0:1",'
        'loop="train",phase="host"} 2.0\n'
        'kungfu_tpu_step_flops{instance="w0:1"} 1000000.0\n'
        'kungfu_tpu_roofline_fraction{bound="best",instance="w0:1"} '
        '0.25\n')
    recs = rep.records_from_cluster_text(text)
    assert recs["w0:1"]["phases"]["compute"] == pytest.approx(8.0)
    assert recs["w0:1"]["roofline"] == pytest.approx(0.25)
    table = rep.render_report(recs)
    assert "w0:1" in table and "25.00%" in table
    blk = rep.bench_block(recs)
    assert blk["metric"] == "kfprof_roofline_fraction_best"
    assert blk["value"] == pytest.approx(0.25)
    assert blk["phase_shares"]["compute"] == pytest.approx(0.8)
    # --dir path: a kfprof_meta.json tree
    d = tmp_path / "prof" / "capture-1-1"
    d.mkdir(parents=True)
    with open(d / "kfprof_meta.json", "w") as f:
        json.dump({"phases": {"train": {"compute": 3.0, "host": 1.0}},
                   "cost": {"flops": 5.0, "hbm_bytes": 7.0},
                   "roofline": {"best": 0.5}}, f)
    drecs = rep.records_from_dir(str(tmp_path / "prof"))
    assert len(drecs) == 1
    (rec,) = drecs.values()
    assert rec["phases"]["compute"] == pytest.approx(3.0)
    assert rec["roofline"] == pytest.approx(0.5)
