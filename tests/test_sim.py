"""kfsim: the cluster-in-a-box simulation tier (kungfu_tpu/sim/).

Unit tier: the deterministic synthetic-progress oracle, the lite-import
contract (a fake trainer must never pull jax — that is what makes
100-process fleets affordable), the sim scenario matrix shape, and the
floor checkers.  Scenario tier: small end-to-end fleets through the
REAL watcher + config server — a no-fault convergence run and a
preemption shrink — kept tiny so they stay tier-1; the big sweeps
(100-worker waves, lease cascades, doctor attribution) live in the
chaos CLI matrix (`make sim-smoke`, docs/chaos.md "Simulation tier").
"""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kungfu_tpu import chaos  # noqa: E402
from kungfu_tpu.chaos import Plan  # noqa: E402
from kungfu_tpu.chaos.runner import (Scenario, floor_violations,  # noqa: E402
                                     scenarios)
from kungfu_tpu.sim import sim_wsum, step_increment  # noqa: E402
from kungfu_tpu.sim.runner import (SimClusterRunner,  # noqa: E402
                                   run_sim_scenario)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _always_disarm():
    yield
    chaos.disarm()


# ------------------------------------------------------ progress oracle
def test_sim_wsum_deterministic_and_seeded():
    assert sim_wsum(0, 12) == sim_wsum(0, 12)
    assert sim_wsum(0, 12) != sim_wsum(1, 12)
    assert sim_wsum(0, 0) == 0.0


def test_sim_wsum_strictly_monotonic():
    prev = 0.0
    for n in range(1, 30):
        cur = sim_wsum(7, n)
        assert cur > prev  # every step adds strictly positive weight
        prev = cur


def test_step_increment_positive_and_rank_free():
    # the increment depends on (seed, step) only: any worker replaying
    # the same steps reproduces the same wsum — that is what lets the
    # invariant sweep compare finals across ranks
    assert all(step_increment(3, t) > 0 for t in range(1, 50))
    assert sum(step_increment(3, t) for t in range(1, 11)) == \
        pytest.approx(sim_wsum(3, 10))


# ------------------------------------------------------- lite imports
def test_sim_worker_imports_no_jax():
    """The whole point of the sim tier: a fake trainer process speaks
    the real host plane without ever importing jax/jaxlib."""
    code = (
        "import os, sys\n"
        "os.environ['KFT_SIM_LITE'] = '1'\n"
        "import kungfu_tpu.sim.trainer\n"
        "import kungfu_tpu.sim.runner\n"
        "bad = [m for m in sys.modules if m.split('.')[0] in "
        "('jax', 'jaxlib')]\n"
        "print(json.dumps(bad)) if (json := __import__('json')) else None\n"
    )
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert json.loads(out.stdout.strip()) == []


# ------------------------------------------------------ matrix shape
def test_sim_scenarios_merged_into_cli_matrix():
    m = scenarios()
    sims = {n for n, sc in m.items() if sc.tier == "sim"}
    assert {"sim-smoke", "sim-preemption-wave-100", "sim-lease-cascade",
            "sim-straggler-doctor-100", "sim-slowlink-doctor-100",
            "sim-slowlink-doctor-clean", "sim-policy-shadow-100",
            "sim-policy-shadow-clean", "sim-policy-act-100",
            "sim-policy-act-flap", "sim-policy-act-smoke",
            "sim-spot-trace",
            "sim-grow-join", "sim-grow-fanout",
            "sim-serve-smoke", "sim-serve-spike-20",
            "sim-serve-imbalance-20", "sim-serve-imbalance-20-clean",
            "sim-serve-replica-kill"} <= sims
    # the kill-mid-action chaos scenario rides its own tier
    assert m["policy-act-kill"].tier == "policy"
    for n in sims:
        sc = m[n]
        assert sc.parent_port is None  # concurrency: OS-assigned ports
        assert sc.timeout_s > 0  # the runner watchdog needs a budget


def test_sim_runner_rejects_real_tier():
    sc = scenarios()["smoke"]
    with pytest.raises(ValueError, match="tier"):
        SimClusterRunner(sc)


# ---------------------------------------------------- floor checkers
def _floor_sc(**kw):
    return Scenario(name="f", desc="", plan=Plan(seed=None), tier="sim",
                    **kw)


def test_min_fired_floor():
    sc = _floor_sc(min_fired=2)
    fired = [{"site": "elastic.step.fence", "action": "kill"}]
    v = floor_violations(sc, fired, [])
    assert v and "fault(s) fired" in v[0]
    assert floor_violations(sc, fired * 2, []) == []


def test_min_served_floor():
    sc = _floor_sc(min_served=10)
    ev = [{"kind": "final", "stream": "w0", "finished": 4},
          {"kind": "final", "stream": "w1", "finished": 3}]
    v = floor_violations(sc, [], ev)
    assert v and "finished only 7" in v[0]
    ev.append({"kind": "final", "stream": "w2", "finished": 3})
    assert floor_violations(sc, [], ev) == []


def test_min_config_versions_floor():
    sc = _floor_sc(min_config_versions=2)
    ev = [{"kind": "config", "version": 1, "epoch": 1},
          {"kind": "config", "version": 1, "epoch": 1}]
    v = floor_violations(sc, [], ev)
    assert v and "config version" in v[0]
    ev.append({"kind": "config", "version": 2, "epoch": 1})
    assert floor_violations(sc, [], ev) == []


# ----------------------------------------------------- scenario tier
def test_sim_fleet_converges_no_faults(tmp_path):
    """4 fake workers under the real watcher: every worker must train
    to target, reach drain consensus over /health leases, and emit the
    same (version, size, wsum) final."""
    sc = Scenario(name="t1-sim-clean", desc="", plan=Plan(seed=None),
                  tier="sim", nprocs=4, target_steps=6,
                  sim_step_s=0.02, sim_seed=5, timeout_s=120.0)
    res = run_sim_scenario(sc, out_root=str(tmp_path), verbose=False)
    assert res.ok, res.violations
    finals = [e for e in res.events if e.get("kind") == "final"]
    assert len(finals) == 4
    assert len({(f["version"], f["size"]) for f in finals}) == 1
    assert finals[0]["wsum"] == pytest.approx(sim_wsum(5, 6))


def test_sim_fleet_absorbs_preemption(tmp_path):
    """One kill at a step fence: the watcher must reap it, CAS-shrink
    the membership, and the survivors must converge on the smaller
    cluster — the no-fresh-start/progress invariants hold throughout."""
    target_steps = 8
    plan = Plan(seed=None).add("elastic.step.fence", "kill", rank=1,
                               step=list(range(2, 50)))
    # The fleet trains for a quarter of a second, and a rank that comes up
    # later than that adopts its peers' finished state and passes no fence
    # at all.  It then dies at the commit that opens its drain, before its
    # lease can show the target: whatever the machine's timing the kill
    # fires, and the others cannot finish without the shrink.
    plan.add("store.save", "kill", rank=1, step=[target_steps])
    sc = Scenario(name="t1-sim-kill", desc="", plan=plan,
                  tier="sim", nprocs=5, target_steps=target_steps,
                  sim_step_s=0.03, min_fired=1, min_config_versions=2,
                  timeout_s=120.0)
    res = run_sim_scenario(sc, out_root=str(tmp_path), verbose=False)
    assert res.ok, res.violations
    finals = [e for e in res.events if e.get("kind") == "final"]
    assert finals and all(f["size"] < 5 for f in finals)
