"""kfnet: the data-movement observability plane (kungfu_tpu.monitor.net,
the rpc byte accounting, the cluster bandwidth matrix, detect_slowlink,
and the kfnet_report CLI — docs/monitoring.md "Transport (kfnet)")."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from kungfu_tpu.monitor import (MONITOR_PORT_OFFSET, MetricsServer,
                                Monitor, RateCounter)
from kungfu_tpu.monitor import cluster as mcluster
from kungfu_tpu.monitor import net
from kungfu_tpu.monitor.doctor import detect_slowlink
from kungfu_tpu.monitor.history import MetricsHistory

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------- rate semantics
class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_rate_counter_decays_to_zero_when_idle():
    clk = _Clock()
    rc = RateCounter(clock=clk)
    rc.add(1000)
    clk.t += 1.0
    assert rc.rate(1.0) == pytest.approx(1000.0)   # first window rolls
    # idle within one period: concurrent readers of the same window
    # must agree EXACTLY, so the held rate is unchanged...
    clk.t += 0.5
    assert rc.rate(1.0) == pytest.approx(1000.0)
    # ...and the roll of the empty window pins it at zero — an idle
    # target never reports its last burst for more than one period
    clk.t += 0.75
    assert rc.rate(1.0) == 0.0
    clk.t += 5.0
    assert rc.rate(1.0) == 0.0


def test_rate_counter_active_window_keeps_last_rate():
    clk = _Clock()
    rc = RateCounter(clock=clk)
    rc.add(1000)
    clk.t += 1.0
    assert rc.rate(1.0) == pytest.approx(1000.0)
    rc.add(10)                     # any traffic in the open window
    clk.t += 0.5
    assert rc.rate(1.0) == pytest.approx(1000.0)   # no decay


def test_rate_counter_partial_first_window_reports():
    clk = _Clock()
    rc = RateCounter(clock=clk)
    rc.add(500)
    clk.t += 0.5
    assert rc.rate(1.0) == pytest.approx(1000.0)


# ----------------------------------------------------- target classes
def test_target_classes():
    assert net.control_target("h:1") == "ctrl:h:1"
    assert net.control_target("ctrl:h:1") == "ctrl:h:1"   # idempotent
    assert net.is_peer_target("10.0.0.1:7001")
    assert not net.is_peer_target("ctrl:10.0.0.1:7001")
    assert not net.is_peer_target("ici")
    assert not net.is_peer_target("state")


# ------------------------------------------------- transfers + ledger
def test_transfer_phase_sum_tracks_wall():
    mon = Monitor()
    t0 = time.perf_counter()
    with net.Transfer("t.op", peer="h:1", monitor=mon) as xf:
        with xf.phase("wire"):
            time.sleep(0.05)
        for _ in range(3):                 # chunk-style re-entry
            with xf.phase("deserialize"):
                time.sleep(0.02)
        xf.add(1 << 20)
    wall = time.perf_counter() - t0
    phase_sum = sum(xf.phases.values())
    assert abs(phase_sum - wall) < 0.10 * wall
    text = mon.render_metrics()
    assert 'kungfu_tpu_state_moved_bytes_total{op="t.op"} 1048576' in text
    assert 'kungfu_tpu_net_phase_seconds' in text
    assert 'kungfu_tpu_state_move_gib_s{op="t.op"}' in text
    assert 'kungfu_tpu_ingress_bytes_total{target="h:1"} 1048576' in text


def test_transfer_records_nothing_on_exception():
    mon = Monitor()
    with pytest.raises(RuntimeError):
        with net.Transfer("t.fail", peer="h:1", monitor=mon) as xf:
            xf.add(999)
            raise RuntimeError("mid-pull death")
    text = mon.render_metrics()
    assert "t.fail" not in text
    assert 'target="h:1"' not in text


def test_record_transfer_ledger_only_without_peer():
    mon = Monitor()
    net.record_transfer("resize.rebuild", nbytes=0, wall=0.5, monitor=mon)
    text = mon.render_metrics()
    assert 'kungfu_tpu_net_transfer_seconds' in text
    assert 'kungfu_tpu_egress_bytes_total' not in text


def test_tree_bytes():
    tree = {"a": np.ones((4, 4), np.float32), "b": None,
            "c": [np.zeros(8, np.float64)]}
    assert net.tree_bytes(tree) == 4 * 4 * 4 + 8 * 8
    assert net.tree_bytes(None) == 0


# ------------------------------------------------ rpc byte accounting
def test_rpc_counts_request_and_response_bytes():
    from kungfu_tpu.monitor import get_monitor
    from kungfu_tpu.utils import rpc as _rpc
    from kungfu_tpu.utils.http import BackgroundHTTPServer
    from http.server import BaseHTTPRequestHandler

    reply = b"pong" * 64

    def factory(_srv):
        class H(BaseHTTPRequestHandler):
            def _answer(self):
                if self.command == "POST":
                    self.rfile.read(
                        int(self.headers.get("Content-Length", 0)))
                self.send_response(200)
                self.send_header("Content-Length", str(len(reply)))
                self.end_headers()
                self.wfile.write(reply)

            do_GET = do_POST = _answer

            def log_message(self, fmt, *args):
                pass
        return H
    srv = BackgroundHTTPServer(factory).start()
    key = f"127.0.0.1:{srv.port}"
    url = f"http://{key}/x"
    mon = get_monitor()

    def totals():
        eg = mon._egress.get(f"ctrl:{key}")
        ig = mon._ingress.get(f"ctrl:{key}")
        return ((eg.total() if eg else 0), (ig.total() if ig else 0))
    try:
        _rpc.call(url)                             # GET: response only
        eg0, ig0 = totals()
        assert eg0 == 0 and ig0 == len(reply)
        body = b"x" * 123
        _rpc.call(url, method="POST", body=body)   # both directions
        eg1, ig1 = totals()
        assert eg1 - eg0 == len(body)
        assert ig1 - ig0 == len(reply)
    finally:
        srv.stop()
        _rpc.reset(url)


# ------------------------------------------------- bandwidth matrix
def test_aggregate_joins_peer_rates_into_matrix():
    mon_a, mon_b = Monitor(), Monitor()
    servers = [MetricsServer(m).start() for m in (mon_a, mon_b)]
    try:
        targets = [("127.0.0.1", s.port - MONITOR_PORT_OFFSET)
                   for s in servers]
        inst_a = f"127.0.0.1:{targets[0][1]}"
        inst_b = f"127.0.0.1:{targets[1][1]}"
        mon_a.ingress(1 << 20, target=inst_b)      # A pulls from B
        mon_b.egress(1 << 20, target=inst_a)       # B's send side
        mon_a.egress(4096, target="ctrl:cs:9")     # control-plane
        mon_a.egress(777, target="ici")            # mesh estimate
        time.sleep(0.05)
        body = mcluster.aggregate(targets)
    finally:
        for s in servers:
            s.stop()
    # one physical link, measured from both ends: B->A
    assert (f'kungfu_tpu_peer_bandwidth_bytes_s{{direction="ingress",'
            f'dst="{inst_a}",src="{inst_b}"}}') in body
    assert (f'kungfu_tpu_peer_bandwidth_bytes_s{{direction="egress",'
            f'dst="{inst_a}",src="{inst_b}"}}') in body
    # non-peer targets still join (classification happens downstream)
    assert 'src="ctrl:cs:9"' not in body           # ctrl is egress: dst
    assert 'dst="ctrl:cs:9"' in body
    # rate gauges render per instance with HELP
    assert "# TYPE kungfu_tpu_ingress_bytes_rate gauge" in body
    rates = mcluster.peer_rates(mon_a.render_metrics())
    assert rates[("ingress", inst_b)] > 0


def test_monitor_prune_targets_drops_departed_peers():
    mon = Monitor()
    mon.egress(100, target="h:1")
    mon.egress(100, target="h:2")
    mon.ingress(100, target="h:1")
    assert 'target="h:1"' in mon.render_metrics()
    mon.prune_targets(["h:1"])
    text = mon.render_metrics()
    assert 'target="h:1"' not in text
    assert 'target="h:2"' in text


# ---------------------------------------------------- detect_slowlink
def _bw_text(ingress_bps: float, egress_bps: float = 1e6,
             peers=("10.0.0.2:7001", "10.0.0.3:7001")) -> str:
    lines = []
    for p in peers:
        lines.append(
            f'kungfu_tpu_ingress_bytes_rate{{target="{p}"}} '
            f'{ingress_bps / len(peers)}')
        lines.append(
            f'kungfu_tpu_egress_bytes_rate{{target="{p}"}} '
            f'{egress_bps / len(peers)}')
    return "\n".join(lines) + "\n"


def _feed(hist, inst, bps, *, windows=3, t0=1000.0, egress_bps=1e6):
    for w in range(windows):
        hist.observe_text(inst, _bw_text(bps, egress_bps), ts=t0 + w)


def test_detect_slowlink_names_the_slow_instance():
    hist = MetricsHistory(window=16)
    for i in range(4):
        _feed(hist, f"10.0.0.{i}:7001", 8e6)
    _feed(hist, "10.0.0.9:7001", 1e6)              # 8x below median
    ranks = {f"10.0.0.{i}:7001": i for i in range(4)}
    ranks["10.0.0.9:7001"] = 9
    fs = detect_slowlink(hist, factor=4.0, min_windows=3, ranks=ranks)
    assert [f.rank for f in fs] == [9]
    f = fs[0]
    assert f.kind == "slowlink"
    assert f.evidence["slow_direction"] == "ingress"   # egress healthy
    assert f.evidence["pull_bw_bps"] == pytest.approx(1e6)
    assert any(k.startswith("bw_from_") for k in f.evidence)


def test_detect_slowlink_flags_both_directions():
    hist = MetricsHistory(window=16)
    for i in range(4):
        _feed(hist, f"10.0.0.{i}:7001", 8e6)
    _feed(hist, "10.0.0.9:7001", 1e6, egress_bps=1e5)
    fs = detect_slowlink(hist, factor=4.0, min_windows=3)
    assert len(fs) == 1
    assert fs[0].evidence["slow_direction"] == "both"


def test_detect_slowlink_negative_on_even_cluster():
    hist = MetricsHistory(window=16)
    for i in range(5):
        _feed(hist, f"10.0.0.{i}:7001", 8e6)
    assert detect_slowlink(hist, factor=4.0, min_windows=3) == []


def test_detect_slowlink_inconclusive_on_idle_cluster():
    hist = MetricsHistory(window=16)
    for i in range(4):
        _feed(hist, f"10.0.0.{i}:7001", 100.0)     # below min_bps
    _feed(hist, "10.0.0.9:7001", 10.0)
    assert detect_slowlink(hist, factor=4.0, min_bps=1024.0,
                           min_windows=3) == []


def test_detect_slowlink_excludes_stale_instances():
    hist = MetricsHistory(window=16)
    for i in range(4):
        _feed(hist, f"10.0.0.{i}:7001", 8e6, t0=1000.0)
    # the ghost: slow rates frozen long before the newest scrape
    _feed(hist, "10.0.0.9:7001", 1e6, t0=100.0)
    fs = detect_slowlink(hist, factor=4.0, min_windows=3, stale_s=60.0)
    assert fs == []


def test_detect_slowlink_needs_two_instances():
    hist = MetricsHistory(window=16)
    _feed(hist, "10.0.0.1:7001", 1e6)
    assert detect_slowlink(hist, min_windows=3) == []


# ------------------------------------------------------- report CLI
def test_kfnet_report_cli_over_saved_history(tmp_path):
    hist = MetricsHistory(window=8)
    _feed(hist, "10.0.0.1:7001", 8e6)
    _feed(hist, "10.0.0.2:7001", 8e6)
    path = str(tmp_path / "hist.jsonl")
    hist.save(path)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "kfnet_report.py"),
         "--history", path, "--json"],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert out.returncode == 0, out.stderr
    doc = json.loads(out.stdout)
    # nodes = the 2 scraped instances plus the one synthetic peer
    # neither of them is (10.0.0.2 appears as both instance and target)
    assert doc["workers"] == 3
    links = {(l["src"], l["dst"], l["direction"]) for l in doc["links"]}
    assert ("10.0.0.2:7001", "10.0.0.1:7001", "ingress") in links
    assert all(l["bytes_per_s"] > 0 for l in doc["links"])


def test_kfnet_report_renders_matrix_text(tmp_path):
    hist = MetricsHistory(window=8)
    _feed(hist, "10.0.0.1:7001", 8e6)
    path = str(tmp_path / "hist.jsonl")
    hist.save(path)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "kfnet_report.py"),
         "--history", path],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert out.returncode == 0, out.stderr
    assert "bandwidth matrix" in out.stdout
    assert "top talkers" in out.stdout


# ------------------------------------------------------ store ledger
def test_model_store_round_trip_feeds_ledger():
    from kungfu_tpu.monitor import get_monitor
    from kungfu_tpu.store import ModelStore

    mon = get_monitor()

    def ledger(op):
        key = ("kungfu_tpu_state_moved_bytes_total", (("op", op),))
        return mon._counters.get(key, 0.0)
    save0, load0 = ledger("store.save"), ledger("store.load")
    store = ModelStore()
    tree = {"w": np.ones((64, 64), np.float32)}
    store.save("m", tree, version=3)
    out = store.request("m", tree, version=3)
    assert out["w"].shape == (64, 64)
    nbytes = 64 * 64 * 4
    assert ledger("store.save") - save0 == nbytes
    assert ledger("store.load") - load0 == nbytes
