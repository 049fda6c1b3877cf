"""kfcheck: every rule fires on its positive fixture and stays quiet on
the matching negative; suppression comments and the baseline behave.

The checker is this repo's step 0 of CI (tools/ci.sh) — these tests are
what keeps its rules from silently rotting as the codebase grows.
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tools.kfcheck import ALL_RULES, Baseline, check_paths  # noqa: E402

RULE_NAMES = {r.name for r in ALL_RULES}


def run_on(tmp_path, source, relpath="kungfu_tpu/mod.py"):
    """Write one fixture file at a repo-relative-looking path and check it."""
    fp = tmp_path / relpath
    fp.parent.mkdir(parents=True, exist_ok=True)
    fp.write_text(textwrap.dedent(source))
    findings, errors = check_paths([fp.parent], ALL_RULES, tmp_path)
    assert not errors, errors
    return findings


def rules_fired(findings):
    return {f.rule for f in findings}


# ------------------------------------------------------ collective-symmetry
def test_collective_symmetry_positive(tmp_path):
    fs = run_on(tmp_path, """
        def adapt(session, rank):
            if rank == 0:
                session.all_reduce(x)
    """)
    assert rules_fired(fs) == {"collective-symmetry"}
    assert "rank-gated" in fs[0].message
    assert fs[0].symbol == "adapt"


def test_collective_symmetry_else_branch_and_peer_id(tmp_path):
    fs = run_on(tmp_path, """
        def teardown(peer):
            if peer.peer_id != leader:
                pass
            else:
                peer.barrier()
    """)
    assert rules_fired(fs) == {"collective-symmetry"}


def test_collective_symmetry_negative(tmp_path):
    # same collective, but the gate is not rank-shaped and the
    # rank-gated branch holds no collective
    fs = run_on(tmp_path, """
        def adapt(session, rank, enabled):
            if enabled:
                session.all_reduce(x)
            if rank == 0:
                print("leader")
    """)
    assert rules_fired(fs) == set()


# --------------------------------------------------------- trace-impurity
def test_trace_impurity_decorated(tmp_path):
    fs = run_on(tmp_path, """
        import jax, time

        @jax.jit
        def step(x):
            t = time.time()
            return x * t
    """)
    assert rules_fired(fs) == {"trace-impurity"}
    assert "time.time" in fs[0].message


def test_trace_impurity_by_reference_and_np_random(tmp_path):
    fs = run_on(tmp_path, """
        import jax
        import numpy as np

        def make_step():
            def body(x):
                return x + np.random.randn()
            return jax.jit(body)
    """)
    assert rules_fired(fs) == {"trace-impurity"}


def test_trace_impurity_same_name_other_scope_is_clean(tmp_path):
    # a method named like a jitted local function elsewhere in the file
    # must NOT inherit its traced-ness (lexical scoping)
    fs = run_on(tmp_path, """
        import jax, time

        def build():
            def run(x):
                return x * 2
            return jax.jit(run)

        class Engine:
            def run(self, xs):
                t0 = time.perf_counter()
                return t0
    """)
    assert rules_fired(fs) == set()


def test_trace_impurity_negative_host_fn(tmp_path):
    fs = run_on(tmp_path, """
        import time

        def host_timer():
            return time.time()
    """)
    assert rules_fired(fs) == set()


# -------------------------------------------------- host-sync-in-hot-path
def test_host_sync_positive(tmp_path):
    fs = run_on(tmp_path, """
        import jax

        def train(steps, step_fn, batches):
            for b in batches:
                loss = step_fn(b)
                print(float(loss))
                jax.device_get(loss)
    """)
    assert rules_fired(fs) == {"host-sync-in-hot-path"}
    # device_get only: implicit float()/int() syncs moved to the
    # host-roundtrip-traced dataflow pass, which proves them from the
    # jit binding instead of guessing from the variable name
    assert len(fs) == 1


def test_host_sync_block_until_ready(tmp_path):
    fs = run_on(tmp_path, """
        def serve_loop(engine, reqs):
            while reqs:
                out = engine.step()
                out.block_until_ready()
    """)
    assert rules_fired(fs) == {"host-sync-in-hot-path"}


def test_host_sync_negative_outside_loop_or_cold_fn(tmp_path):
    fs = run_on(tmp_path, """
        import jax

        def train(step_fn, batches):
            for b in batches:
                loss = step_fn(b)
            return float(loss)     # after the loop: one sync, fine

        def debug_dump(loss):
            while True:
                jax.device_get(loss)   # not a hot-path function name
                break
    """)
    assert rules_fired(fs) == set()


def test_host_sync_tree_map_on_commit_path(tmp_path):
    """The kfsnap bug class: whole-tree per-leaf D2H on a step/commit
    path — direct callable, lambda wrapper, and device_get all flagged,
    and the message points at the kfsnap replacement."""
    fs = run_on(tmp_path, """
        import jax
        import numpy as np

        def _commit(self):
            self._host = jax.tree_util.tree_map(np.asarray, self._params)

        def resize(self):
            h = jax.tree_util.tree_map(lambda t: np.asarray(t),
                                       self.params)

        def sync_state(self):
            return jax.tree_util.tree_map(jax.device_get, self.opt)
    """)
    assert rules_fired(fs) == {"host-sync-in-hot-path"}
    assert len(fs) == 3
    assert all("elastic.snapshot" in f.message for f in fs)


def test_host_sync_tree_map_cold_path_ok(tmp_path):
    """A one-time init/broadcast helper may materialise the whole tree;
    only step/commit-path function names are in scope."""
    fs = run_on(tmp_path, """
        import jax
        import numpy as np

        def _init_state(self, init_params):
            self._host = jax.tree_util.tree_map(np.asarray, init_params)

        def broadcast_host_tree(tree):
            return jax.tree_util.tree_map(np.asarray, tree)

        def _commit(self):
            # tree_map without a sync callable is fine
            return jax.tree_util.tree_map(lambda t: t * 2, self.params)
    """)
    assert rules_fired(fs) == set()


# ------------------------------------------------------------ silent-except
def test_silent_except_positive_scoped_dirs(tmp_path):
    src = """
        def poll(url):
            try:
                fetch(url)
            except Exception:
                pass
    """
    fs = run_on(tmp_path, src, relpath="kungfu_tpu/elastic/mod.py")
    assert rules_fired(fs) == {"silent-except"}
    # the observability plane is in scope too (kftrace + monitor)
    fs = run_on(tmp_path, src, relpath="kungfu_tpu/trace/mod.py")
    assert rules_fired(fs) == {"silent-except"}
    fs = run_on(tmp_path, src, relpath="kungfu_tpu/monitor/mod.py")
    assert rules_fired(fs) == {"silent-except"}
    # same code OUTSIDE the control/observability planes is out of scope
    fs = run_on(tmp_path, src, relpath="kungfu_tpu/models/mod.py")
    assert rules_fired(fs) == set()


def test_silent_except_covers_kfdoctor_modules(tmp_path):
    """The kfdoctor diagnosis plane (monitor/doctor.py, history.py) is
    inside the silent-except scope — a doctor that eats its own errors
    is worse than no doctor."""
    src = """
        def diagnose(history):
            try:
                detect(history)
            except Exception:
                pass
    """
    for rel in ("kungfu_tpu/monitor/doctor.py",
                "kungfu_tpu/monitor/history.py"):
        fs = run_on(tmp_path, src, relpath=rel)
        assert rules_fired(fs) == {"silent-except"}, rel


def test_silent_except_covers_kfprof(tmp_path):
    """The kfprof attribution plane (monitor/profiler.py) is inside the
    silent-except scope — a profiler that eats a failed capture would
    report 'all healthy' precisely when the capture path broke."""
    src = """
        def handle_profile_request(path):
            try:
                start_capture(path)
            except Exception:
                pass
    """
    fs = run_on(tmp_path, src, relpath="kungfu_tpu/monitor/profiler.py")
    assert rules_fired(fs) == {"silent-except"}


def test_silent_except_covers_slo_plane(tmp_path):
    """The serving SLO plane (serving/slo.py) and its load harness
    (tools/kfload.py) are inside the silent-except scope — a swallowed
    error there silently corrupts the compliance/burn numbers the
    plane exists to report.  The REST of serving/ stays out of scope
    (scoped by file, like utils/rpc.py)."""
    src = """
        def publish(journal):
            try:
                journal.evaluate()
            except Exception:
                pass
    """
    for rel in ("kungfu_tpu/serving/slo.py", "tools/kfload.py"):
        fs = run_on(tmp_path, src, relpath=rel)
        assert rules_fired(fs) == {"silent-except"}, rel
    fs = run_on(tmp_path, src, relpath="kungfu_tpu/serving/engine.py")
    # the earlier slo.py fixture shares the directory: scope the
    # assertion to the engine.py file itself
    assert {f.rule for f in fs if f.path.endswith("engine.py")} == set()


def test_silent_except_covers_kfnet_tools(tmp_path):
    """The kfnet report/bench CLIs are inside the silent-except scope —
    a report that eats a parse failure renders an empty matrix that
    reads as 'no traffic', and a bench that eats a pull error commits
    a zero baseline."""
    src = """
        def render(url):
            try:
                fetch_matrix(url)
            except Exception:
                pass
    """
    for rel in ("tools/kfnet_report.py", "tools/bench_p2p.py"):
        fs = run_on(tmp_path, src, relpath=rel)
        assert rules_fired(fs) == {"silent-except"}, rel


def test_silent_except_covers_kfsim(tmp_path):
    """The kfsim fake-trainer plane (kungfu_tpu/sim/) is inside the
    silent-except scope — it speaks the real control plane, and a fake
    trainer that eats a config/heartbeat error would green-wash exactly
    the chaos scenarios built to redden it."""
    src = """
        def poll(url):
            try:
                fetch_config(url)
            except Exception:
                pass
    """
    fs = run_on(tmp_path, src, relpath="kungfu_tpu/sim/mod.py")
    assert rules_fired(fs) == {"silent-except"}


def test_silent_except_covers_kfpolicy(tmp_path):
    """The kfpolicy decision plane (kungfu_tpu/policy/ and its
    tools/kfpolicy.py CLI) is inside the silent-except scope — an
    engine that eats a rule error records a silently wrong (or
    silently missing) proposal, which is exactly the failure the
    shadow ledger exists to make auditable."""
    src = """
        def tick(rules, ctx):
            try:
                rules.evaluate(ctx)
            except Exception:
                pass
    """
    for rel in ("kungfu_tpu/policy/engine.py", "tools/kfpolicy.py"):
        fs = run_on(tmp_path, src, relpath=rel)
        assert rules_fired(fs) == {"silent-except"}, rel


def test_silent_except_bare_and_negative(tmp_path):
    fs = run_on(tmp_path, """
        def a(url):
            try:
                fetch(url)
            except:
                return None

        def b(url):
            try:
                fetch(url)
            except Exception as e:
                log.warning("poll failed: %s", e)   # logged: not silent

        def c(url):
            try:
                fetch(url)
            except (OSError, ValueError):
                pass                                 # narrow: not broad
    """, relpath="kungfu_tpu/launcher/mod.py")
    assert [f.symbol for f in fs] == ["a"]


# --------------------------------------------------------- unjoined-thread
def test_unjoined_thread_positive(tmp_path):
    fs = run_on(tmp_path, """
        import threading

        def start(fn):
            t = threading.Thread(target=fn)
            t.start()
    """)
    assert rules_fired(fs) == {"unjoined-thread"}


def test_unjoined_thread_negatives(tmp_path):
    fs = run_on(tmp_path, """
        import threading

        def daemonized(fn):
            t = threading.Thread(target=fn, daemon=True)
            t.start()

        def joined(fn):
            t = threading.Thread(target=fn)
            t.start()
            t.join()

        class S:
            def start(self, fn):
                self._t = threading.Thread(target=fn)
                self._t.start()

            def stop(self):
                self._t.join(timeout=5)
    """)
    assert rules_fired(fs) == set()


# ------------------------------------------------------------- accum-dtype
def test_accum_dtype_positive_ops_scope(tmp_path):
    src = """
        import jax.numpy as jnp

        def kernel(a, b):
            return jnp.einsum("ij,jk->ik", a, b)
    """
    fs = run_on(tmp_path, src, relpath="kungfu_tpu/ops/k.py")
    assert rules_fired(fs) == {"accum-dtype"}
    # outside ops/ the rule does not apply
    fs = run_on(tmp_path, src, relpath="kungfu_tpu/models/m.py")
    assert rules_fired(fs) == set()


def test_accum_dtype_matmul_operator_and_negative(tmp_path):
    fs = run_on(tmp_path, """
        import jax, jax.numpy as jnp

        def bad(a, b):
            return a @ b

        def good(a, b):
            return jax.lax.dot_general(
                a, b, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    """, relpath="kungfu_tpu/ops/k.py")
    assert [f.symbol for f in fs] == ["bad"]


# ------------------------------------------------------------- suppression
def test_suppression_same_line_and_standalone_comment(tmp_path):
    fs = run_on(tmp_path, """
        def adapt(session, rank):
            if rank == 0:
                session.all_reduce(x)  # kfcheck: disable=collective-symmetry
            if rank == 1:
                # kfcheck: disable=collective-symmetry
                session.barrier()
    """)
    assert fs == []


def test_suppression_is_per_rule(tmp_path):
    # disabling an unrelated rule must not silence the finding
    fs = run_on(tmp_path, """
        def adapt(session, rank):
            if rank == 0:
                session.all_reduce(x)  # kfcheck: disable=accum-dtype
    """)
    assert rules_fired(fs) == {"collective-symmetry"}


# ---------------------------------------------------------------- baseline
def _one_finding(tmp_path):
    return run_on(tmp_path, """
        def adapt(session, rank):
            if rank == 0:
                session.all_reduce(x)
    """)


def test_baseline_grandfathers_and_detects_stale(tmp_path):
    fs = _one_finding(tmp_path)
    bl_path = tmp_path / "baseline.json"
    bl_path.write_text(Baseline.render(fs, {fs[0].key(): "known; audited"}))
    bl = Baseline.load(bl_path)
    new, old, stale = bl.split(fs)
    assert (len(new), len(old), len(stale)) == (0, 1, 0)
    # finding fixed -> entry goes stale
    new, old, stale = bl.split([])
    assert (len(new), len(old), len(stale)) == (0, 0, 1)


def test_baseline_is_line_number_insensitive(tmp_path):
    fs = _one_finding(tmp_path)
    bl_path = tmp_path / "baseline.json"
    bl_path.write_text(Baseline.render(fs, {fs[0].key(): "known"}))
    # same finding, shifted down by unrelated edits above it
    shifted = run_on(tmp_path, """
        import os

        X = 1


        def adapt(session, rank):
            if rank == 0:
                session.all_reduce(x)
    """)
    new, old, stale = Baseline.load(bl_path).split(shifted)
    assert (len(new), len(old), len(stale)) == (0, 1, 0)


def test_baseline_requires_justification(tmp_path):
    bl_path = tmp_path / "baseline.json"
    bl_path.write_text(json.dumps({"version": 1, "entries": [
        {"rule": "accum-dtype", "path": "p.py", "symbol": "f",
         "snippet": "a @ b", "why": "  "}]}))
    with pytest.raises(ValueError, match="justification"):
        Baseline.load(bl_path)


# --------------------------------------------------------------------- CLI
def _cli(args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "tools.kfcheck", *args],
        cwd=cwd, capture_output=True, text=True, timeout=120)


def test_cli_shipped_tree_is_clean():
    """Acceptance gate: `make lint` (== this invocation) exits 0 on the
    tree as shipped."""
    r = _cli([])
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_fails_on_introduced_violation(tmp_path):
    """Acceptance gate: introducing a fixture violation flips the exit
    code to non-zero (and names the rule)."""
    bad = tmp_path / "kungfu_tpu" / "ops" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("def f(a, b):\n    return a @ b\n")
    r = _cli(["--no-baseline", str(bad)])
    assert r.returncode == 1, r.stdout + r.stderr
    assert "accum-dtype" in r.stdout


def test_cli_list_rules_covers_all():
    r = _cli(["--list-rules"])
    assert r.returncode == 0
    for name in RULE_NAMES:
        assert name in r.stdout


def test_shipped_baseline_entries_all_justified():
    data = json.loads(
        (REPO / "tools" / "kfcheck" / "baseline.json").read_text())
    for e in data["entries"]:
        assert e["why"].strip() and "TODO" not in e["why"], e


# ================================================== whole-program passes
from tools.kfcheck.engine import Module  # noqa: E402
from tools.kfcheck.facts import (FactCache, analyze,  # noqa: E402
                                 collect_facts, scan_native)
from tools.kfcheck.wprogram import (ALL_PASSES, edit_distance,  # noqa: E402
                                    run_passes)

PASS_NAMES = {p.name for p in ALL_PASSES}


def run_program(tmp_path, files):
    """Write a synthetic tree and run only the whole-program passes."""
    for rel, src in files.items():
        fp = tmp_path / rel
        fp.parent.mkdir(parents=True, exist_ok=True)
        fp.write_text(textwrap.dedent(src))
    _, facts, errors = analyze([tmp_path], [], [], tmp_path,
                               use_cache=False)
    assert not errors, errors
    facts.update(scan_native(tmp_path))
    return run_passes(facts)


MINI_REGISTRY = """
    def _def(name, type, default, doc="", **kw):
        pass
    _def("KFT_GOOD_KNOB", "int", 1, "a registered knob")
"""


# --------------------------------------------------------- lock-discipline
def test_lock_discipline_positive(tmp_path):
    fs = run_program(tmp_path, {"kungfu_tpu/w.py": """
        import threading

        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self._thread = threading.Thread(target=self._run)
                self._results = {}

            def _run(self):
                self._results["k"] = 1

            def snapshot(self):
                return dict(self._results)
    """})
    assert rules_fired(fs) == {"lock-discipline"}
    assert "_results" in fs[0].message and fs[0].symbol == "Worker.snapshot"


def test_lock_discipline_negative_locked_both_sides(tmp_path):
    fs = run_program(tmp_path, {"kungfu_tpu/w.py": """
        import threading

        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self._thread = threading.Thread(target=self._run)
                self._results = {}

            def _run(self):
                with self._lock:
                    self._results["k"] = 1

            def snapshot(self):
                with self._lock:
                    return dict(self._results)
    """})
    assert fs == []


def test_lock_discipline_exemptions(tmp_path):
    # thread-safe containers (Queue), __init__ accesses, the _locked
    # method-name convention, and flag writes of constants do not fire
    fs = run_program(tmp_path, {"kungfu_tpu/w.py": """
        import queue
        import threading

        class Worker:
            def __init__(self):
                self._cv = threading.Condition()
                self._q = queue.Queue()
                self._thread = threading.Thread(target=self._run)
                self._done = False
                self._err = None

            def _run(self):
                self._q.put(1)
                self._done = True
                with self._cv:
                    self._err = compute()

            def _peek_locked(self):
                return self._err

            def drain(self):
                if self._done:
                    return self._q.get()
                with self._cv:
                    return self._peek_locked()
    """})
    assert fs == []


def test_lock_discipline_thread_subclass_run(tmp_path):
    fs = run_program(tmp_path, {"kungfu_tpu/w.py": """
        import threading

        class Sampler(threading.Thread):
            def __init__(self):
                super().__init__()
                self.seen = {}

            def run(self):
                self.seen.setdefault("a", 1)

            def report(self):
                return list(self.seen.values())
    """})
    assert rules_fired(fs) == {"lock-discipline"}


# ----------------------------------------------------------- knob-registry
def test_knob_registry_flags_raw_read_and_unregistered(tmp_path):
    fs = run_program(tmp_path, {
        "kungfu_tpu/utils/knobs.py": MINI_REGISTRY,
        "kungfu_tpu/mod.py": """
            import os
            A = os.environ.get("KFT_GOOD_KNOB")
            B = os.environ["KFT_MYSTERY_KNOB"]
        """})
    assert rules_fired(fs) == {"knob-registry"}
    msgs = "\n".join(f.message for f in fs)
    # registered-but-raw read AND unregistered name both fire
    assert "raw environment read of `KFT_GOOD_KNOB`" in msgs
    assert "raw environment read of `KFT_MYSTERY_KNOB`" in msgs
    assert "`KFT_MYSTERY_KNOB` is not registered" in msgs


def test_knob_registry_resolves_module_constants(tmp_path):
    fs = run_program(tmp_path, {
        "kungfu_tpu/utils/knobs.py": MINI_REGISTRY,
        "kungfu_tpu/mod.py": """
            import os
            ENV = "KFT_GOOD_KNOB"
            value = os.getenv(ENV)
        """})
    assert any("raw environment read of `KFT_GOOD_KNOB`" in f.message
               for f in fs)


def test_knob_registry_negative_and_tests_exemption(tmp_path):
    fs = run_program(tmp_path, {
        "kungfu_tpu/utils/knobs.py": MINI_REGISTRY,
        "kungfu_tpu/mod.py": """
            from .utils import knobs
            value = knobs.get("KFT_GOOD_KNOB")
        """,
        # tests may read env directly — only unregistered names flag
        "tests/test_mod.py": """
            import os
            os.environ.get("KFT_GOOD_KNOB")
        """})
    assert fs == []


def test_knob_registry_covers_native_reads(tmp_path):
    fs = run_program(tmp_path, {
        "kungfu_tpu/utils/knobs.py": MINI_REGISTRY,
        "native/src/peer.cc": """\
            static double t = env_double("KFT_NATIVE_ONLY_KNOB", 1.0);
        """})
    assert rules_fired(fs) == {"knob-registry"}
    assert "native=True" in fs[0].message


def test_deleting_a_registry_entry_fails_ci(tmp_path):
    """Acceptance gate: drop one migrated knob's _def from the REAL
    registry and the real call site turns into a finding (CI step 0
    runs this checker, so this is the red build)."""
    reg = (REPO / "kungfu_tpu" / "utils" / "knobs.py").read_text()
    assert '"KFT_HEARTBEAT_S"' in reg, "fixture went stale"
    # renaming the registered string IS deleting the KFT_HEARTBEAT_S
    # entry, without having to excise a multi-line _def() call
    files = {
        "kungfu_tpu/utils/knobs.py": reg.replace(
            '"KFT_HEARTBEAT_S"', '"KFT_HEARTBEAT_ZZ"'),
        "kungfu_tpu/elastic/heartbeat.py":
            (REPO / "kungfu_tpu" / "elastic" / "heartbeat.py").read_text(),
    }
    for rel, src in files.items():
        fp = tmp_path / rel
        fp.parent.mkdir(parents=True, exist_ok=True)
        fp.write_text(src)
    _, facts, errors = analyze([tmp_path], [], [], tmp_path,
                               use_cache=False)
    assert not errors, errors
    fs = run_passes(facts)
    assert any(f.rule == "knob-registry" and "KFT_HEARTBEAT_S" in f.message
               for f in fs), [f.message for f in fs]


# ----------------------------------------------------- metrics-consistency
METRICS_OK = {
    "kungfu_tpu/monitor/__init__.py": """
        _HELP = {
            "kungfu_tpu_step_seconds": "Step wall time.",
        }

        class Monitor:
            def observe(self, metric, value):
                pass

        def publish(m):
            m.observe("kungfu_tpu_step_seconds", 1.0)
    """,
    "kungfu_tpu/monitor/doctor.py": """
        def diagnose(history, inst):
            return history.series(inst, "kungfu_tpu_step_seconds")
    """,
}


def test_metrics_consistency_negative(tmp_path):
    assert run_program(tmp_path, METRICS_OK) == []


def test_metrics_consumed_but_never_published(tmp_path):
    files = dict(METRICS_OK)
    files["kungfu_tpu/monitor/doctor.py"] = """
        def diagnose(history, inst):
            return history.series(inst, "kungfu_tpu_phantom_seconds")
    """
    fs = run_program(tmp_path, files)
    assert rules_fired(fs) == {"metrics-consistency"}
    assert "kungfu_tpu_phantom_seconds" in fs[0].message
    assert "never" in fs[0].message or "publishes it" in fs[0].message


def test_metrics_published_without_help(tmp_path):
    files = dict(METRICS_OK)
    files["kungfu_tpu/serving.py"] = """
        def emit(m):
            m.set_gauge("kungfu_tpu_undocumented_gauge", 2.0)
    """
    fs = run_program(tmp_path, files)
    assert rules_fired(fs) == {"metrics-consistency"}
    assert "without HELP" in fs[0].message


def test_metrics_near_miss_spelling(tmp_path):
    files = dict(METRICS_OK)
    # established name appears twice (publish + HELP); the typo once,
    # in a non-consumer file so only the near-miss check can catch it
    files["kungfu_tpu/extra.py"] = """
        NAME = "kungfu_tpu_step_second"
    """
    fs = run_program(tmp_path, files)
    assert rules_fired(fs) == {"metrics-consistency"}
    assert "probable misspelling" in fs[0].message


def test_metrics_summary_suffixes_normalize(tmp_path):
    files = dict(METRICS_OK)
    files["kungfu_tpu/monitor/cluster.py"] = """
        import re
        PAT = re.compile(r"^kungfu_tpu_step_seconds_sum")
    """
    assert run_program(tmp_path, files) == []


def test_kfload_is_a_metrics_consumer(tmp_path):
    """tools/kfload.py parses /metrics expositions (fleet bench knee
    detection): any metric literal there must resolve against a real
    published family, even outside a series() call."""
    files = dict(METRICS_OK)
    files["tools/kfload.py"] = """
        THRESH = {"kungfu_tpu_fleet_phantom_gauge": 2.0}
    """
    fs = run_program(tmp_path, files)
    assert rules_fired(fs) == {"metrics-consistency"}
    assert "kungfu_tpu_fleet_phantom_gauge" in fs[0].message
    files["tools/kfload.py"] = """
        THRESH = {"kungfu_tpu_step_seconds": 2.0}
    """
    assert run_program(tmp_path, files) == []


def test_misspelled_doctor_metric_fails_ci(tmp_path):
    """Acceptance gate: misspell one doctor-consumed metric name in the
    REAL sources and CI step 0 goes red."""
    mon = (REPO / "kungfu_tpu" / "monitor" / "__init__.py").read_text()
    doc = (REPO / "kungfu_tpu" / "monitor" / "doctor.py").read_text()
    assert '"kungfu_tpu_step_seconds"' in doc, "fixture went stale"
    doc = doc.replace('"kungfu_tpu_step_seconds"',
                      '"kungfu_tpu_step_secondz"', 1)
    files = {"kungfu_tpu/monitor/__init__.py": mon,
             "kungfu_tpu/monitor/doctor.py": doc}
    for rel, src in files.items():
        fp = tmp_path / rel
        fp.parent.mkdir(parents=True, exist_ok=True)
        fp.write_text(src)
    _, facts, errors = analyze([tmp_path], [], [], tmp_path,
                               use_cache=False)
    assert not errors, errors
    fs = run_passes(facts)
    assert any(f.rule == "metrics-consistency"
               and "kungfu_tpu_step_secondz" in f.message
               for f in fs), [f.message for f in fs]


# ----------------------------------------------------------- chaos-coverage
CHAOS_OK = {
    "kungfu_tpu/chaos/sites.py": """
        SITES = {
            "layer.op.phase": "where and what",
        }
    """,
    "kungfu_tpu/elastic/core.py": """
        from . import chaos

        def step():
            chaos.point("layer.op.phase", rank=0)
    """,
    "tests/test_sites.py": """
        def test_fault():
            plan = Plan().add("layer.op.phase", "exception")
    """,
}


def test_chaos_coverage_negative(tmp_path):
    assert run_program(tmp_path, CHAOS_OK) == []


def test_chaos_point_not_registered(tmp_path):
    files = dict(CHAOS_OK)
    files["kungfu_tpu/elastic/core.py"] = """
        from . import chaos

        def step():
            chaos.point("layer.op.phase", rank=0)
            chaos.point("rogue.site.name")
    """
    fs = run_program(tmp_path, files)
    assert rules_fired(fs) == {"chaos-coverage"}
    assert "rogue.site.name" in fs[0].message
    assert "not registered" in fs[0].message


def test_chaos_dead_catalogue_entry_and_untested_site(tmp_path):
    files = dict(CHAOS_OK)
    files["kungfu_tpu/chaos/sites.py"] = """
        SITES = {
            "layer.op.phase": "covered",
            "layer.op.dead": "registered but never fired",
            "layer.op.untested": "fired but never referenced",
        }
    """
    files["kungfu_tpu/elastic/core.py"] = """
        from . import chaos

        def step():
            chaos.point("layer.op.phase", rank=0)
            chaos.point("layer.op.untested")
    """
    fs = run_program(tmp_path, files)
    msgs = "\n".join(f.message for f in fs)
    assert "`layer.op.dead` is registered but no chaos.point" in msgs
    assert "`layer.op.untested` has a live chaos.point but no" in msgs


def test_chaos_plan_ref_to_unknown_site(tmp_path):
    files = dict(CHAOS_OK)
    files["tests/test_sites.py"] = """
        def test_fault():
            plan = Plan().add("layer.op.phase", "exception")
            bad = Plan().add("layer.op.typo", "kill")
    """
    fs = run_program(tmp_path, files)
    assert rules_fired(fs) == {"chaos-coverage"}
    assert "unknown site `layer.op.typo`" in fs[0].message


# ------------------------------------------------- suppression / baseline
def test_program_pass_suppression_comment(tmp_path):
    fs = run_program(tmp_path, {
        "kungfu_tpu/utils/knobs.py": MINI_REGISTRY,
        "kungfu_tpu/mod.py": """
            import os
            # kfcheck: disable=knob-registry
            A = os.environ.get("KFT_GOOD_KNOB")
        """})
    assert fs == []


def test_program_findings_use_baseline_machinery(tmp_path):
    fs = run_program(tmp_path, {
        "kungfu_tpu/utils/knobs.py": MINI_REGISTRY,
        "kungfu_tpu/mod.py": """
            import os
            A = os.environ.get("KFT_GOOD_KNOB")
        """})
    assert len(fs) == 1
    bl_path = tmp_path / "baseline.json"
    bl_path.write_text(Baseline.render(fs, {fs[0].key(): "migration WIP"}))
    new, old, stale = Baseline.load(bl_path).split(fs)
    assert (len(new), len(old), len(stale)) == (0, 1, 0)


# ------------------------------------------------- dataflow: use-after-donate
DONATING_TRAINER = """
    import jax

    class Trainer:
        def __init__(self, body):
            self._step = jax.jit(body, donate_argnums=(0, 1))

        def train(self, params, opt, batches):
            for b in batches:
                new_p, new_opt, loss = self._step(params, opt, b)
                print(params)
                params, opt = new_p, new_opt
            return params
"""


def test_use_after_donate_positive(tmp_path):
    fs = run_program(tmp_path, {"kungfu_tpu/t.py": DONATING_TRAINER})
    assert rules_fired(fs) == {"use-after-donate"}
    assert len(fs) == 1
    assert "`params`" in fs[0].message and "donated position 0" \
        in fs[0].message
    assert fs[0].snippet.strip() == "print(params)"


def test_use_after_donate_negative_rebound_in_statement(tmp_path):
    fs = run_program(tmp_path, {"kungfu_tpu/t.py": """
        import jax

        class Trainer:
            def __init__(self, body):
                self._step = jax.jit(body, donate_argnums=(0, 1))

            def train(self, params, opt, batches):
                for b in batches:
                    params, opt, loss = self._step(params, opt, b)
                    print(loss)
                return params
    """})
    assert fs == []


def test_use_after_donate_suppression(tmp_path):
    src = DONATING_TRAINER.replace(
        "print(params)",
        "print(params)  # kfcheck: disable=use-after-donate")
    fs = run_program(tmp_path, {"kungfu_tpu/t.py": src})
    assert fs == []


def test_use_after_donate_never_rebound_attr(tmp_path):
    fs = run_program(tmp_path, {"kungfu_tpu/t.py": """
        import jax

        class Trainer:
            def __init__(self, body):
                self._step = jax.jit(body, donate_argnums=(0,))

            def step(self, batch):
                loss = self._step(self.params, batch)
                return loss
    """})
    assert rules_fired(fs) == {"use-after-donate"}
    assert "never rebound" in fs[0].message


def test_use_after_donate_outside_kungfu_tpu_exempt(tmp_path):
    # tests/benches may re-read donated inputs to assert CPU semantics
    fs = run_program(tmp_path, {"tools/bench_x.py": DONATING_TRAINER})
    assert fs == []


def test_use_after_donate_gated_factory_closure(tmp_path):
    """The repo idiom end to end: a module-level factory whose closure
    calls a conditionally-donated jit, consumed cross-file through a
    self-attr binding; the donate=True call site makes a post-call read
    a finding, the donate=False twin stays quiet."""
    factory = """
        import jax

        def build_step(loss_fn, opt, mesh, donate=False):
            def body(p, s, b):
                return p, s, b
            jit_kwargs = {"donate_argnums": (0, 1)} if donate else {}
            jitted = jax.jit(body, **jit_kwargs)

            def step(p, s, b):
                p2, s2, out = jitted(p, s, b)
                return p2, s2, out
            return step
    """
    trainer = """
        from .train import build_step

        class Trainer:
            def _install(self, n):
                self._step = build_step(self.loss, self.opt, self.mesh,
                                        donate={flag})

            def step(self, p, s, batch):
                p2, s2, loss = self._step(p, s, batch)
                return p2, s2, p
    """
    fs = run_program(tmp_path, {
        "kungfu_tpu/train.py": factory,
        "kungfu_tpu/tr.py": trainer.format(flag="True")})
    assert "use-after-donate" in rules_fired(fs)
    assert any("via factory `build_step`" in f.message for f in fs)
    fs = run_program(tmp_path, {
        "kungfu_tpu/train.py": factory,
        "kungfu_tpu/tr.py": trainer.format(flag="False")})
    assert fs == []


def test_use_after_donate_kfsnap_async_dispatch(tmp_path):
    """The temporal hazard: an async snapshot holds device refs while a
    later donated step invalidates them; drain() before the step clears
    it."""
    src = """
        import jax

        class MP:
            def __init__(self, body, committer):
                self._step = jax.jit(body, donate_argnums=(0, 1))
                self._committer = committer

            def _commit(self, publish):
                self._committer.initiate((self._params, self._opt),
                                         publish)

            def step(self, batch):
                {drain}self._params, self._opt, loss = self._step(
                    self._params, self._opt, batch)
                return loss
    """
    fs = run_program(tmp_path, {
        "kungfu_tpu/mp.py": src.format(drain="")})
    assert rules_fired(fs) == {"use-after-donate"}
    assert "async snapshot dispatch" in fs[0].message
    assert "initiate" in fs[0].snippet
    fs = run_program(tmp_path, {
        "kungfu_tpu/mp.py": src.format(
            drain="self._committer.drain()\n                ")})
    assert fs == []


def test_use_after_donate_real_training_read_fails_ci(tmp_path):
    """Acceptance gate: inject a post-call read of a donated arg into
    the REAL build_train_step closure and the checker (CI step 0) goes
    red."""
    src = (REPO / "kungfu_tpu" / "training.py").read_text()
    marker = "        return p, s, losses\n"
    assert marker in src, "fixture went stale"
    files = {"kungfu_tpu/training.py": src.replace(
        marker,
        "        _dbg = stacked_params\n" + marker, 1)}
    for rel, text in files.items():
        fp = tmp_path / rel
        fp.parent.mkdir(parents=True, exist_ok=True)
        fp.write_text(text)
    _, facts, errors = analyze([tmp_path], [], [], tmp_path,
                               use_cache=False)
    assert not errors, errors
    fs = run_passes(facts)
    assert any(f.rule == "use-after-donate" and "stacked_params"
               in f.message for f in fs), [f.render() for f in fs]


# ------------------------------------------------ dataflow: sharding-mismatch
def test_sharding_mismatch_positive_and_negative(tmp_path):
    factory = """
        import jax

        def build_step(loss_fn, opt, mesh, donate=False):
            def body(p, s, b):
                return p, s, b
            jit_kwargs = {"donate_argnums": (0, 1)} if donate else {}
            jitted = jax.jit(body, **jit_kwargs)

            def step(p, s, b):
                p, s, out = jitted(p, s, b)
                return p, s, out
            return step
    """
    trainer = """
        from .train import build_step

        class Trainer:
            def _install(self, n):
                self.mesh = flat_mesh(n=n)
                self.params = restack(self._host, n, {layout})
                self._step = build_step(self.loss, self.opt, self.mesh,
                                        donate=True)

            def step(self, batch):
                self.params, self.opt_state, loss = self._step(
                    self.params, self.opt_state, batch)
                return loss
    """
    fs = run_program(tmp_path, {
        "kungfu_tpu/train.py": factory,
        "kungfu_tpu/tr.py": trainer.format(layout="other_mesh(n)")})
    assert rules_fired(fs) == {"sharding-mismatch"}
    assert "`self.params`" in fs[0].message and "other_mesh" \
        in fs[0].message
    # laid out against the same mesh the step was built with: quiet
    fs = run_program(tmp_path, {
        "kungfu_tpu/train.py": factory,
        "kungfu_tpu/tr.py": trainer.format(layout="self.mesh")})
    assert fs == []


def test_sharding_mismatch_suppression(tmp_path):
    fs = run_program(tmp_path, {"kungfu_tpu/t.py": """
        import jax

        class T:
            def _install(self, n):
                # kfcheck: disable=sharding-mismatch
                self.params = restack(self._host, n, other_mesh(n))
                self._step = jax.jit(body, donate_argnums=(0,))

            def step(self, b):
                self.params, loss = self._step(self.params, b)
                return loss
    """})
    assert fs == []


def test_sharding_mismatch_real_elastic_relayout_fails_ci(tmp_path):
    """Acceptance gate: re-lay out the REAL elastic trainer's donated
    params against a different mesh than the step was built with and
    the checker goes red."""
    tr = (REPO / "kungfu_tpu" / "elastic" / "trainer.py").read_text()
    marker = "self.params = _restack(self._host_params, n, self.mesh)"
    assert marker in tr, "fixture went stale"
    files = {
        "kungfu_tpu/elastic/trainer.py": tr.replace(
            marker,
            "self.params = _restack(self._host_params, n, "
            "flat_mesh(n=n))", 1),
        "kungfu_tpu/training.py":
            (REPO / "kungfu_tpu" / "training.py").read_text(),
    }
    for rel, text in files.items():
        fp = tmp_path / rel
        fp.parent.mkdir(parents=True, exist_ok=True)
        fp.write_text(text)
    _, facts, errors = analyze([tmp_path], [], [], tmp_path,
                               use_cache=False)
    assert not errors, errors
    fs = run_passes(facts)
    assert any(f.rule == "sharding-mismatch" and "self.params"
               in f.message for f in fs), [f.render() for f in fs]


# -------------------------------------------- dataflow: host-roundtrip-traced
def test_host_roundtrip_sync_in_hot_loop(tmp_path):
    fs = run_program(tmp_path, {"kungfu_tpu/e.py": """
        import jax

        class Engine:
            def __init__(self, body):
                self._decode = jax.jit(body)

            def serve(self, reqs):
                out = []
                for r in reqs:
                    toks = self._decode(r)
                    out.append(float(toks))
                return out
    """})
    assert rules_fired(fs) == {"host-roundtrip-traced"}
    assert "inside a loop of `serve`" in fs[0].message


def test_host_roundtrip_negative_single_sync_rebind(tmp_path):
    # the engine.py idiom: ONE deliberate np.asarray sync rebinds the
    # name to a host array; the loop then reads free numpy memory
    fs = run_program(tmp_path, {"kungfu_tpu/e.py": """
        import jax
        import numpy as np

        class Engine:
            def __init__(self, body):
                self._decode = jax.jit(body)

            def serve(self, reqs):
                toks = self._decode(reqs)
                toks = np.asarray(toks)
                out = []
                for j in range(4):
                    out.append(int(toks[j]))
                return out
    """})
    assert fs == []


def test_host_roundtrip_feedback(tmp_path):
    fs = run_program(tmp_path, {"kungfu_tpu/e.py": """
        import jax
        import numpy as np

        class Engine:
            def __init__(self, body):
                self._decode = jax.jit(body)

            def serve(self, batch):
                toks = self._decode(batch)
                host = np.asarray(toks)
                out = self._decode(host)
                return out
    """})
    assert rules_fired(fs) == {"host-roundtrip-traced"}
    assert "fed back" in fs[0].message


def test_host_roundtrip_cold_frame_exempt(tmp_path):
    # a sync inside a loop of a cold (non-hot-path) frame is fine
    fs = run_program(tmp_path, {"kungfu_tpu/e.py": """
        import jax

        class Engine:
            def __init__(self, body):
                self._decode = jax.jit(body)

            def warmup(self, reqs):
                for r in reqs:
                    toks = self._decode(r)
                    print(float(toks))
    """})
    assert fs == []


# ----------------------------------------------------------- facts cache
def test_fact_cache_hit_and_invalidation(tmp_path):
    fp = tmp_path / "m.py"
    fp.write_text("import os\nA = os.environ.get('KFT_X_KNOB')\n")
    cache_path = tmp_path / ".cache.json"
    cache = FactCache(cache_path)
    mod = Module("m.py", fp.read_text())
    facts = collect_facts(mod)
    cache.put("m.py", fp.stat(), facts)
    cache.save()
    # hit: same mtime/size round-trips through JSON
    reloaded = FactCache(cache_path)
    assert reloaded.get("m.py", fp.stat()) == json.loads(
        json.dumps(facts))
    # miss: content change invalidates
    fp.write_text("import os\nA = os.environ.get('KFT_Y_KNOB')  # xx\n")
    assert reloaded.get("m.py", fp.stat()) is None


def test_analyze_uses_cache_for_context_files(tmp_path):
    ctx = tmp_path / "tools" / "helper.py"
    ctx.parent.mkdir(parents=True)
    ctx.write_text("X = 'KFT_CACHED_KNOB'\n")
    cache_path = tmp_path / ".cache.json"
    kw = dict(use_cache=True, cache_path=cache_path)
    _, facts1, _ = analyze([], [tmp_path / "tools"], [], tmp_path, **kw)
    # poison the cached entry; an (unchanged) second run must serve it
    data = json.loads(cache_path.read_text())
    entry = data["files"]["tools/helper.py"]
    entry["facts"]["knob_literals"][0]["name"] = "KFT_FROM_CACHE"
    cache_path.write_text(json.dumps(data))
    _, facts2, _ = analyze([], [tmp_path / "tools"], [], tmp_path, **kw)
    assert facts2["tools/helper.py"]["knob_literals"][0]["name"] == \
        "KFT_FROM_CACHE"


def test_edit_distance():
    assert edit_distance("abc", "abc", 2) == 0
    assert edit_distance("abc", "abd", 2) == 1
    assert edit_distance("abc", "bd", 2) == 2
    assert edit_distance("abcdef", "uvwxyz", 2) > 2


# ------------------------------------------------------ clean-tree pins
def _repo_program_findings():
    _, facts, errors = analyze(
        [Path("kungfu_tpu")], [Path("tools"), Path("tests")], [],
        REPO, use_cache=False)
    assert not errors, errors
    facts.update(scan_native(REPO))
    return run_passes(facts)


@pytest.fixture(scope="module")
def repo_program_findings():
    return _repo_program_findings()


@pytest.mark.parametrize("pass_name", sorted(PASS_NAMES))
def test_shipped_tree_clean_per_pass(repo_program_findings, pass_name):
    """Per-pass pin: on today's tree every pass is clean modulo the
    justified baseline."""
    from tools.kfcheck.__main__ import DEFAULT_BASELINE
    bl = Baseline.load(DEFAULT_BASELINE)
    mine = [f for f in repo_program_findings if f.rule == pass_name]
    new, _, _ = bl.split(mine)
    assert new == [], [f.render() for f in new]


def test_cli_json_output():
    r = _cli(["--json"])
    assert r.returncode == 0, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert set(payload) == {"findings", "stale", "errors"}
    for f in payload["findings"]:
        assert f["baselined"] is True  # clean tree: only baselined ones


def test_cli_list_rules_covers_passes():
    r = _cli(["--list-rules"])
    for name in PASS_NAMES:
        assert name in r.stdout
    assert "whole-program pass" in r.stdout


def test_cli_program_mode_on_synthetic_tree(tmp_path):
    (tmp_path / "kungfu_tpu").mkdir(parents=True)
    (tmp_path / "kungfu_tpu" / "mod.py").write_text(
        'import os\nA = os.environ.get("KFT_ORPHAN_KNOB")\n')
    r = _cli(["--program", "--root", str(tmp_path), "--no-baseline",
              "--no-cache", str(tmp_path)])
    assert r.returncode == 1, r.stdout + r.stderr
    assert "knob-registry" in r.stdout


# ================================================ protocol passes (phase 4)
import re  # noqa: E402

from tools.kfcheck.protocol import (JOURNAL_FAMILIES,  # noqa: E402
                                    SEQLOCK_SHAPES)


def test_protocol_registries_name_real_files():
    """Anti-drift pin: every registry path matches a shipped file (a
    renamed journal/seqlock file must be re-registered, not silently
    unchecked)."""
    tree = [p.relative_to(REPO).as_posix()
            for p in (REPO / "kungfu_tpu").rglob("*.py")]
    for fam in JOURNAL_FAMILIES:
        assert any(re.search(fam["path"], p) for p in tree), fam["name"]
    for sh in SEQLOCK_SHAPES:
        assert any(re.search(sh["path"], p) for p in tree), sh["name"]


# ------------------------------------------------------------ lock-ordering
def test_lock_ordering_cycle_nested_with(tmp_path):
    fs = run_program(tmp_path, {"kungfu_tpu/m.py": """
        import threading

        _lock_a = threading.Lock()
        _lock_b = threading.Lock()

        def f():
            with _lock_a:
                with _lock_b:
                    pass

        def g():
            with _lock_b:
                with _lock_a:
                    pass
    """})
    assert rules_fired(fs) == {"lock-ordering"}
    assert "lock-order cycle" in fs[0].message
    assert "_lock_a" in fs[0].message and "_lock_b" in fs[0].message


def test_lock_ordering_consistent_order_clean(tmp_path):
    fs = run_program(tmp_path, {"kungfu_tpu/m.py": """
        import threading

        _lock_a = threading.Lock()
        _lock_b = threading.Lock()

        def f():
            with _lock_a:
                with _lock_b:
                    pass

        def g():
            with _lock_a:
                with _lock_b:
                    pass
    """})
    assert fs == []


def test_lock_ordering_cycle_across_files_call_through(tmp_path):
    fs = run_program(tmp_path, {
        "kungfu_tpu/__init__.py": "",
        "kungfu_tpu/a.py": """
            import threading
            from . import b

            _alock = threading.Lock()

            def fa():
                with _alock:
                    b.fb()
        """,
        "kungfu_tpu/b.py": """
            import threading
            from . import a

            _block = threading.Lock()

            def fb():
                with _block:
                    pass

            def fg():
                with _block:
                    a.fa()
        """})
    assert rules_fired(fs) == {"lock-ordering"}
    assert any("cycle" in f.message for f in fs)


def test_lock_ordering_nonreentrant_reacquire_via_callee(tmp_path):
    src = """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.{kind}()

            def outer(self):
                with self._lock:
                    self.inner()

            def inner(self):
                with self._lock:
                    pass
    """
    fs = run_program(tmp_path,
                     {"kungfu_tpu/m.py": src.format(kind="Lock")})
    assert rules_fired(fs) == {"lock-ordering"}
    assert "re-acquire" in fs[0].message or "acquires it again" \
        in fs[0].message
    # reentrant RLock: same shape, no deadlock
    fs = run_program(tmp_path,
                     {"kungfu_tpu/m.py": src.format(kind="RLock")})
    assert fs == []


def test_lock_ordering_suppression(tmp_path):
    fs = run_program(tmp_path, {"kungfu_tpu/m.py": """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def f(self):
                with self._lock:
                    # kfcheck: disable=lock-ordering
                    with self._lock:
                        pass
    """})
    assert fs == []


# ----------------------------------------------------------- wal-discipline
LEDGER_SHAPE = """
    import json
    import os

    class DecisionLedger:
        def _write(self, doc):
            self._fh.write(json.dumps(doc) + "\\n")
            {flush}
            {fsync}

        def append(self, d):
            {pre}self._write(d.to_dict())
            self._ring.append(d)
            self._by_seq[d.seq] = d
"""


def _ledger_tree(flush="self._fh.flush()",
                 fsync="os.fsync(self._fh.fileno())", pre=""):
    return {"kungfu_tpu/policy/ledger.py": LEDGER_SHAPE.format(
        flush=flush, fsync=fsync, pre=pre)}


def test_wal_triple_clean(tmp_path):
    assert run_program(tmp_path, _ledger_tree()) == []


def test_wal_flush_without_fsync(tmp_path):
    fs = run_program(tmp_path, _ledger_tree(fsync="pass"))
    assert rules_fired(fs) == {"wal-discipline"}
    assert "never fsyncs" in fs[0].message


def test_wal_write_without_flush(tmp_path):
    fs = run_program(tmp_path, _ledger_tree(flush="pass", fsync="pass"))
    assert rules_fired(fs) == {"wal-discipline"}
    assert "without flushing" in fs[0].message


def test_wal_fsync_wrong_fd(tmp_path):
    fs = run_program(tmp_path, _ledger_tree(
        fsync="os.fsync(self._other.fileno())"))
    assert rules_fired(fs) == {"wal-discipline"}
    assert "wrong fd" in fs[0].message


def test_wal_side_effect_before_journal(tmp_path):
    fs = run_program(tmp_path, _ledger_tree(
        pre="self._ring.append(d)\n            "))
    assert rules_fired(fs) == {"wal-discipline"}
    assert "BEFORE the journal append" in fs[0].message
    assert "_ring" in fs[0].message


def test_wal_registry_drift_is_a_finding(tmp_path):
    # a journal-family file whose declared writer vanished (renamed)
    # must go red, not silently unchecked
    fs = run_program(tmp_path, {"kungfu_tpu/policy/ledger.py": """
        import json

        class DecisionLedger:
            def _write_renamed(self, doc):
                self._fh.write(json.dumps(doc) + "\\n")
    """})
    assert rules_fired(fs) == {"wal-discipline"}
    assert "registry" in fs[0].message and "stale" in fs[0].message


def test_wal_suppression(tmp_path):
    tree = _ledger_tree(fsync="pass")
    src = tree["kungfu_tpu/policy/ledger.py"]
    src = src.replace(
        "            self._fh.flush()",
        "            # kfcheck: disable=wal-discipline\n"
        "            self._fh.flush()")
    assert run_program(
        tmp_path, {"kungfu_tpu/policy/ledger.py": src}) == []


# ------------------------------------------------------------ version-fence
def test_version_fence_unfenced_put_config(tmp_path):
    fs = run_program(tmp_path, {"kungfu_tpu/elastic/m.py": """
        def seed(url, cluster):
            put_config(url, cluster)
    """})
    assert rules_fired(fs) == {"version-fence"}
    assert "if_version" in fs[0].message


def test_version_fence_fenced_put_config_clean(tmp_path):
    fs = run_program(tmp_path, {"kungfu_tpu/elastic/m.py": """
        def resize(url, cluster, version):
            put_config(url, cluster, if_version=version)
    """})
    assert fs == []


def test_version_fence_out_of_scope_clean(tmp_path):
    # chaos/sim tiers deliberately drive unfenced writes to exercise
    # the server's CAS rejection
    fs = run_program(tmp_path, {"kungfu_tpu/chaos/m.py": """
        def stir(url, cluster):
            put_config(url, cluster)
    """})
    assert fs == []


def test_version_fence_put_builder_without_if_match(tmp_path):
    src = """
        def put_thing(url, body{sig}):
            {hdr}return rpc_call(url, method="PUT", body=body{use})
    """
    fs = run_program(tmp_path, {"kungfu_tpu/elastic/m.py": src.format(
        sig="", hdr="", use="")})
    assert rules_fired(fs) == {"version-fence"}
    assert "If-Match" in fs[0].message
    fs = run_program(tmp_path, {"kungfu_tpu/elastic/m.py": src.format(
        sig=", version",
        hdr='headers = {"If-Match": str(version)}\n            ',
        use=", headers=headers")})
    assert fs == []


def test_version_fence_versioned_store_save(tmp_path):
    src = """
        def push(p, name, b, seq):
            p.save(f"kftsh:{{name}}", b{fence})
    """
    fs = run_program(tmp_path, {"kungfu_tpu/elastic/m.py": src.format(
        fence="")})
    assert rules_fired(fs) == {"version-fence"}
    assert "version=" in fs[0].message
    fs = run_program(tmp_path, {"kungfu_tpu/elastic/m.py": src.format(
        fence=", version=seq")})
    assert fs == []


def test_version_fence_suppression(tmp_path):
    fs = run_program(tmp_path, {"kungfu_tpu/elastic/m.py": """
        def seed(url, cluster):
            # kfcheck: disable=version-fence
            put_config(url, cluster)
    """})
    assert fs == []


# ------------------------------------------------------------ seqlock-shape
SEQ_WRITER = """
    import threading
    import numpy as np

    _lock = threading.RLock()

    def publish(seg, payload, nbytes):
        hdr = seg.hdr
        {body}
"""

SEQ_WRITER_OK = """with _lock:
            seg.gen += 1
            hdr[1] = seg.gen
            hdr[2] = nbytes
            np.copyto(seg.payload, payload)
            seg.gen += 1
            hdr[1] = seg.gen"""


def test_seqlock_writer_clean(tmp_path):
    fs = run_program(tmp_path, {
        "kungfu_tpu/store/shm.py": SEQ_WRITER.format(body=SEQ_WRITER_OK)})
    assert fs == []


def test_seqlock_writer_single_bump(tmp_path):
    body = """with _lock:
            seg.gen += 1
            hdr[1] = seg.gen
            np.copyto(seg.payload, payload)"""
    fs = run_program(tmp_path, {
        "kungfu_tpu/store/shm.py": SEQ_WRITER.format(body=body)})
    assert rules_fired(fs) == {"seqlock-shape"}
    assert "bump" in fs[0].message


def test_seqlock_writer_not_under_lock(tmp_path):
    body = """seg.gen += 1
        np.copyto(seg.payload, payload)
        seg.gen += 1"""
    fs = run_program(tmp_path, {
        "kungfu_tpu/store/shm.py": SEQ_WRITER.format(body=body)})
    assert rules_fired(fs) == {"seqlock-shape"}
    assert "not entirely under one lock" in fs[0].message


SEQ_READER = """
    import numpy as np

    def read_into(seg, dst, want_gen, retries=2):
        hdr = seg.hdr
        src = seg.payload
        {loop}
            g0 = int(hdr[1])
            if g0 != want_gen:
                return False
            np.copyto(dst, src)
            {recheck}
        return False
"""


def test_seqlock_reader_clean(tmp_path):
    fs = run_program(tmp_path, {
        "kungfu_tpu/store/shm.py": SEQ_READER.format(
            loop="for _ in range(max(1, retries)):",
            recheck="if int(hdr[1]) == g0:\n                return True")})
    assert fs == []


def test_seqlock_reader_unbounded_retry(tmp_path):
    fs = run_program(tmp_path, {
        "kungfu_tpu/store/shm.py": SEQ_READER.format(
            loop="while True:",
            recheck="if int(hdr[1]) == g0:\n                return True")})
    assert rules_fired(fs) == {"seqlock-shape"}
    assert "while" in fs[0].message and "bound" in fs[0].message.lower()


def test_seqlock_reader_no_recheck_after_copy(tmp_path):
    fs = run_program(tmp_path, {
        "kungfu_tpu/store/shm.py": SEQ_READER.format(
            loop="for _ in range(max(1, retries)):",
            recheck="return True")})
    assert rules_fired(fs) == {"seqlock-shape"}
    assert "re-check" in fs[0].message or "pinning" in fs[0].message


def test_seqlock_real_shm_is_shape_clean(tmp_path):
    src = (REPO / "kungfu_tpu" / "store" / "shm.py").read_text()
    fp = tmp_path / "kungfu_tpu" / "store" / "shm.py"
    fp.parent.mkdir(parents=True)
    fp.write_text(src)
    _, facts, errors = analyze([tmp_path], [], [], tmp_path,
                               use_cache=False)
    assert not errors, errors
    fs = [f for f in run_passes(facts)
          if f.rule in ("seqlock-shape", "lock-ordering")]
    assert fs == [], [f.render() for f in fs]


def test_seqlock_suppression(tmp_path):
    body = """with _lock:
            # kfcheck: disable=seqlock-shape
            seg.gen += 1
            np.copyto(seg.payload, payload)"""
    src = SEQ_WRITER.format(body=body)
    # the single-bump finding anchors at the writer def line
    src = src.replace("    def publish(",
                      "    # kfcheck: disable=seqlock-shape\n"
                      "    def publish(")
    fs = run_program(tmp_path, {"kungfu_tpu/store/shm.py": src})
    assert fs == []


# --------------------------------------------------------- thread-lifecycle
def test_thread_lifecycle_daemon_loop_without_stop(tmp_path):
    fs = run_program(tmp_path, {"kungfu_tpu/w.py": """
        import threading

        class W:
            def __init__(self):
                self._results = {}
                self._thread = threading.Thread(target=self._run,
                                                daemon=True)
                self._thread.start()

            def _run(self):
                while True:
                    self._results["k"] = object()
    """})
    assert rules_fired(fs) == {"thread-lifecycle"}
    assert "stop" in fs[0].message and "_results" in fs[0].message


def test_thread_lifecycle_stop_event_loop_clean(tmp_path):
    fs = run_program(tmp_path, {"kungfu_tpu/w.py": """
        import threading

        class W:
            def __init__(self):
                self._results = {}
                self._stop = threading.Event()
                self._thread = threading.Thread(target=self._run,
                                                daemon=True)
                self._thread.start()

            def _run(self):
                while not self._stop.wait(0.5):
                    self._results["k"] = object()
    """})
    assert [f for f in fs if f.rule == "thread-lifecycle"] == []


def test_thread_lifecycle_start_before_attrs(tmp_path):
    src = """
        import threading

        class W:
            def __init__(self, q):
                {a}self._thread = threading.Thread(target=self._run)
                self._thread.start()
                {b}
            def _run(self):
                return self._q
    """
    fs = run_program(tmp_path, {"kungfu_tpu/w.py": src.format(
        a="", b="self._q = q\n")})
    assert rules_fired(fs) == {"thread-lifecycle"}
    assert "before assigning" in fs[0].message and "_q" in fs[0].message
    fs = run_program(tmp_path, {"kungfu_tpu/w.py": src.format(
        a="self._q = q\n                ", b="")})
    assert fs == []


def test_thread_lifecycle_unbounded_join_on_stop_path(tmp_path):
    src = """
        import threading

        class W:
            def __init__(self):
                self._stop = threading.Event()
                self._thread = threading.Thread(target=self._run)

            def _run(self):
                pass

            def stop(self):
                self._stop.set()
                self._thread.join({timeout})

            def wait_done(self):
                self._thread.join()
    """
    fs = run_program(tmp_path, {"kungfu_tpu/w.py": src.format(timeout="")})
    assert rules_fired(fs) == {"thread-lifecycle"}
    assert "stop" in fs[0].message and "deadline" in fs[0].message
    # bounded join on the stop path: clean (wait_done is not a stop
    # path, so its unbounded join is a deliberate blocking wait)
    fs = run_program(tmp_path, {
        "kungfu_tpu/w.py": src.format(timeout="timeout=5.0")})
    assert fs == []


def test_thread_lifecycle_ignores_non_thread_handles(tmp_path):
    # launcher/watch.py regression: worker-process handles and futures
    # have start()/join() too — not this pass's business
    fs = run_program(tmp_path, {"kungfu_tpu/w.py": """
        class Watcher:
            def _spawn(self, peer):
                proc = self.job.new_proc(peer)
                proc.start()
                self.current[peer] = proc

            def fetch(self, pend):
                host = pend.join()
                return host
    """})
    assert fs == []


def test_thread_lifecycle_suppression(tmp_path):
    fs = run_program(tmp_path, {"kungfu_tpu/w.py": """
        import threading

        class W:
            def __init__(self):
                self._stop = threading.Event()
                self._thread = threading.Thread(target=self._run)

            def _run(self):
                pass

            def stop(self):
                # kfcheck: disable=thread-lifecycle
                self._thread.join()
    """})
    assert fs == []


# -------------------------------------- real-source acceptance gates (ph 4)
def _analyze_mutated(tmp_path, files):
    for rel, text in files.items():
        fp = tmp_path / rel
        fp.parent.mkdir(parents=True, exist_ok=True)
        fp.write_text(text)
    _, facts, errors = analyze([tmp_path], [], [], tmp_path,
                               use_cache=False)
    assert not errors, errors
    return run_passes(facts)


def test_wal_real_ledger_fsync_removal_fails_ci(tmp_path):
    """Acceptance gate (a): remove the os.fsync from the REAL policy
    ledger and the checker (CI step 0) goes red."""
    src = (REPO / "kungfu_tpu" / "policy" / "ledger.py").read_text()
    marker = "            os.fsync(self._fh.fileno())\n"
    assert marker in src, "fixture went stale"
    fs = _analyze_mutated(tmp_path, {
        "kungfu_tpu/policy/ledger.py": src.replace(marker, "", 1)})
    hits = [f for f in fs if f.rule == "wal-discipline"
            and "DecisionLedger._write" in f.message]
    assert hits, [f.render() for f in fs]
    r = _cli(["--program", "--no-baseline", "--no-cache",
              "--root", str(tmp_path), str(tmp_path)])
    assert r.returncode == 1 and "wal-discipline" in r.stdout, \
        r.stdout + r.stderr


def test_wal_real_action_wal_fsync_removal_fails_ci(tmp_path):
    """Acceptance gate (kfact): remove the os.fsync from the REAL
    action WAL and the checker (CI step 0) goes red — an executor
    whose intent records can silently vanish must not ship."""
    src = (REPO / "kungfu_tpu" / "policy" / "executor.py").read_text()
    marker = "            os.fsync(self._fh.fileno())\n"
    assert marker in src, "fixture went stale"
    fs = _analyze_mutated(tmp_path, {
        "kungfu_tpu/policy/executor.py": src.replace(marker, "", 1)})
    hits = [f for f in fs if f.rule == "wal-discipline"
            and "ActionWAL._write" in f.message]
    assert hits, [f.render() for f in fs]
    r = _cli(["--program", "--no-baseline", "--no-cache",
              "--root", str(tmp_path), str(tmp_path)])
    assert r.returncode == 1 and "wal-discipline" in r.stdout, \
        r.stdout + r.stderr


def test_wal_real_action_wal_journal_precedes_cas(tmp_path):
    """Acceptance gate (kfact): hoist the executor's CAS ABOVE the
    intent append inside _execute's caller and the journal-before-
    action ordering pass goes red.  Proven on a synthetic family
    member: the real _dispatch's append must precede put_config."""
    src = (REPO / "kungfu_tpu" / "policy" / "executor.py").read_text()
    fs = _analyze_mutated(tmp_path, {
        "kungfu_tpu/policy/executor.py": src})
    assert not [f.render() for f in fs
                if f.rule == "wal-discipline"], \
        "the real executor must pass the wal-discipline ordering"
    mutated = src.replace(
        "        from .. import chaos as _chaos\n"
        "        self._wal.append(intent)\n",
        "        from .. import chaos as _chaos\n"
        "        from ..elastic.config_server import put_config\n"
        "        put_config(self.config_url, None)\n"
        "        self._wal.append(intent)\n", 1)
    assert mutated != src, "fixture went stale"
    fs = _analyze_mutated(tmp_path, {
        "kungfu_tpu/policy/executor.py": mutated})
    hits = [f for f in fs if f.rule == "wal-discipline"
            and "_dispatch" in f.message]
    assert hits, [f.render() for f in fs]


def test_lock_ordering_real_monitor_inversion_fails_ci(tmp_path):
    """Acceptance gate (b): nest the REAL profiler's two module locks in
    opposite orders on two paths and the checker goes red with a cycle."""
    src = (REPO / "kungfu_tpu" / "monitor" / "profiler.py").read_text()
    m1 = ("    with _state_lock:\n"
          "        flops, hbm = _last_cost\n")
    m2 = ("    with _capture_seq_lock:\n"
          "        _capture_seq += 1\n"
          "        seq = _capture_seq\n")
    assert m1 in src and m2 in src, "fixture went stale"
    mutated = src.replace(m1, (
        "    with _state_lock:\n"
        "        with _capture_seq_lock:\n"
        "            flops, hbm = _last_cost\n"), 1)
    mutated = mutated.replace(m2, (
        "    with _capture_seq_lock:\n"
        "        with _state_lock:\n"
        "            _capture_seq += 1\n"
        "            seq = _capture_seq\n"), 1)
    fs = _analyze_mutated(tmp_path, {
        "kungfu_tpu/monitor/profiler.py": mutated})
    hits = [f for f in fs if f.rule == "lock-ordering"
            and "cycle" in f.message]
    assert hits, [f.render() for f in fs]
    assert any("_state_lock" in f.message and "_capture_seq_lock"
               in f.message for f in hits)
    r = _cli(["--program", "--no-baseline", "--no-cache",
              "--root", str(tmp_path), str(tmp_path)])
    assert r.returncode == 1 and "lock-ordering" in r.stdout, \
        r.stdout + r.stderr


def test_version_fence_real_dropped_if_match_fails_ci(tmp_path):
    """Acceptance gate (c): drop the If-Match header from the REAL
    config-server CAS builder and the checker goes red."""
    src = (REPO / "kungfu_tpu" / "elastic" / "config_server.py").read_text()
    marker = ("    if if_version is not None:\n"
              "        headers[\"If-Match\"] = str(if_version)\n")
    assert marker in src, "fixture went stale"
    fs = _analyze_mutated(tmp_path, {
        "kungfu_tpu/elastic/config_server.py": src.replace(marker, "", 1)})
    hits = [f for f in fs if f.rule == "version-fence"
            and "If-Match" in f.message]
    assert hits, [f.render() for f in fs]
    assert any("put_config" in f.message for f in hits)
    r = _cli(["--program", "--no-baseline", "--no-cache",
              "--root", str(tmp_path), str(tmp_path)])
    assert r.returncode == 1 and "version-fence" in r.stdout, \
        r.stdout + r.stderr


# ------------------------------------------- burned-down-fix regressions
def test_ledger_append_journals_before_publish(tmp_path):
    """Regression for the wal-discipline fix: the decision must be
    durable BEFORE it appears in the ring the /decisions endpoint
    serves."""
    from kungfu_tpu.policy.ledger import Decision, DecisionLedger
    led = DecisionLedger(ring=4, path=str(tmp_path / "led.jsonl"))
    order = []
    orig = led._write

    def spy(doc):
        order.append((doc["kind"], len(led._ring)))
        orig(doc)

    led._write = spy  # type: ignore[method-assign]
    led.append(Decision(seq=0, tick=1, ts=1.0, rule="r",
                        verdict="would-act", action="exclude"))
    assert order == [("decision", 0)]  # journaled while ring still empty


def test_ledger_annotate_journals_before_patch(tmp_path):
    from kungfu_tpu.policy.ledger import Decision, DecisionLedger
    led = DecisionLedger(ring=4, path=str(tmp_path / "led.jsonl"))
    d = Decision(seq=0, tick=1, ts=1.0, rule="r",
                 verdict="would-act", action="exclude")
    led.append(d)
    at_write = []
    orig = led._write

    def spy(doc):
        if doc["kind"] == "annotation":
            at_write.append(d.outcome)
        orig(doc)

    led._write = spy  # type: ignore[method-assign]
    assert led.annotate(0, "vindicated", reason="died")
    assert at_write == [None]  # journaled before the ring copy mutated
    assert d.outcome == "vindicated"


# --------------------------------------------------- phase-4 cache behavior
def test_facts_schema_bump_invalidates_cache(tmp_path, monkeypatch):
    import tools.kfcheck.facts as fmod
    fp = tmp_path / "m.py"
    fp.write_text("X = 1\n")
    cp = tmp_path / ".cache.json"
    c = fmod.FactCache(cp)
    c.put("m.py", fp.stat(), {"fake": 1})
    c.save()
    assert fmod.FactCache(cp).get("m.py", fp.stat()) is not None
    monkeypatch.setattr(fmod, "FACTS_SCHEMA", fmod.FACTS_SCHEMA + 1)
    assert fmod.FactCache(cp).files == {}


def test_analyze_serves_primary_facts_from_cache(tmp_path):
    """The warm-run budget holds because PRIMARY files' fact collection
    (the dataflow + protocol walks) is served from the cache too — the
    rules re-parse, the collectors don't rerun."""
    pr = tmp_path / "kungfu_tpu" / "m.py"
    pr.parent.mkdir(parents=True)
    pr.write_text("X = 'KFT_CACHED_KNOB'\n")
    cp = tmp_path / ".cache.json"
    kw = dict(use_cache=True, cache_path=cp)
    analyze([tmp_path / "kungfu_tpu"], [], [], tmp_path, **kw)
    data = json.loads(cp.read_text())
    entry = data["files"]["kungfu_tpu/m.py"]
    entry["facts"]["knob_literals"][0]["name"] = "KFT_FROM_CACHE"
    cp.write_text(json.dumps(data))
    _, facts, _ = analyze([tmp_path / "kungfu_tpu"], [], [], tmp_path,
                          **kw)
    assert facts["kungfu_tpu/m.py"]["knob_literals"][0]["name"] == \
        "KFT_FROM_CACHE"


def test_phase4_passes_run_from_warm_cache(tmp_path):
    """--fast's contract: phase 4 consumes facts["protocol"] straight
    from the warm cache (poisoned cache => poisoned finding)."""
    src = tmp_path / "kungfu_tpu" / "elastic" / "x.py"
    src.parent.mkdir(parents=True)
    src.write_text("def seed(url, c):\n    pass\n")
    cp = tmp_path / ".cache.json"
    kw = dict(use_cache=True, cache_path=cp)
    analyze([], [tmp_path / "kungfu_tpu"], [], tmp_path, **kw)
    data = json.loads(cp.read_text())
    entry = data["files"]["kungfu_tpu/elastic/x.py"]
    entry["facts"]["protocol"]["fence"]["mutators"].append(
        {"line": 2, "symbol": "seed", "snippet": "put_config(url, c)",
         "name": "put_config", "npos": 2, "kwargs": []})
    cp.write_text(json.dumps(data))
    _, facts, _ = analyze([], [tmp_path / "kungfu_tpu"], [], tmp_path,
                          **kw)
    fs = run_passes(facts)
    assert any(f.rule == "version-fence" for f in fs), \
        [f.render() for f in fs]


def test_warm_repo_run_stays_fast():
    """What keeps a warm repo-wide run inside the budget the --fast CI
    lane is sized for: it takes every file's facts from the cache the
    run before it left.  The cache file is written only after a ``put``
    (``FactCache.save``), so a warm run leaves it as it found it."""
    from tools.kfcheck.facts import DEFAULT_CACHE
    _cli([])  # warm
    before = DEFAULT_CACHE.stat()
    r = _cli([])
    assert r.returncode == 0, r.stdout + r.stderr
    after = DEFAULT_CACHE.stat()
    assert (after.st_mtime_ns, after.st_size) == \
        (before.st_mtime_ns, before.st_size), "the warm run re-collected"


# --------------------------------------------------- phase-4 CLI plumbing
def test_silent_except_scope_covers_protocol():
    from tools.kfcheck.rules import SilentExcept
    assert re.search(SilentExcept.path_filter,
                     "tools/kfcheck/protocol.py")


def test_cli_pass_filter_focused_gate(tmp_path):
    (tmp_path / "kungfu_tpu" / "elastic").mkdir(parents=True)
    (tmp_path / "kungfu_tpu" / "elastic" / "x.py").write_text(
        "def seed(url, cluster):\n    put_config(url, cluster)\n")
    base = ["--no-baseline", "--no-cache", "--root", str(tmp_path),
            str(tmp_path)]
    r = _cli(["--pass", "version-fence", *base])
    assert r.returncode == 1 and "version-fence" in r.stdout, \
        r.stdout + r.stderr
    # the filter really filters: a different pass sees nothing here
    r = _cli(["--pass", "knob-registry", *base])
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_pass_unknown_name():
    r = _cli(["--pass", "no-such-pass"])
    assert r.returncode == 2
    assert "unknown pass" in r.stderr


def test_cli_pass_version_fence_repo_green():
    # the exact focused invocation ci.sh step 0h runs
    r = _cli(["--program", "--pass", "version-fence"])
    assert r.returncode == 0, r.stdout + r.stderr
