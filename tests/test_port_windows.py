"""The ports of test processes that run side by side: the layout that
tests/testutil.py describes, held to the offsets the library uses."""
import itertools
import os

import pytest

import testutil
from kungfu_tpu import distributed
from kungfu_tpu.monitor import MONITOR_PORT_OFFSET
from kungfu_tpu.plan import hostspec
from kungfu_tpu.sim import runner as sim_runner
from kungfu_tpu.utils import knobs

EPHEMERAL_FLOOR = 32768
WORKERS = [None] + [f"gw{k}" for k in range(testutil.PORT_WINDOWS)]


def _base(worker):
    env = {} if worker is None else {"PYTEST_XDIST_WORKER": worker}
    testutil.claim_port_window(env)
    return knobs.get("KFT_BASE_PORT", env=env)


def _ranges(worker):
    base = _base(worker)
    runner = base + hostspec.DEFAULT_RUNNER_PORT - hostspec.DEFAULT_WORKER_PORT
    sim = sim_runner._sim_base_port(base)
    return {
        "runner": range(runner, runner + 1),
        "workers": range(base, base + testutil.WORKER_PORTS),
        # peer 0's port + 1000 + the cluster version, for as many
        # versions as a test's cluster goes through
        "coordinators": range(base + distributed._COORD_PORT_OFFSET,
                              base + distributed._COORD_PORT_OFFSET
                              + testutil.WORKER_PORTS),
        "monitor": range(base + MONITOR_PORT_OFFSET,
                         base + MONITOR_PORT_OFFSET + testutil.WORKER_PORTS),
        "sim": range(sim, sim + sim_runner.SIM_PORTS),
        "sim metrics": range(sim + MONITOR_PORT_OFFSET,
                             sim + MONITOR_PORT_OFFSET + sim_runner.SIM_PORTS),
    }


def _overlap(a, b):
    return a.start < b.stop and b.start < a.stop


@pytest.mark.parametrize("worker", WORKERS, ids=lambda w: w or "no-xdist")
def test_a_window_shares_no_port(worker):
    mine = _ranges(worker)
    assert 1124 <= _base(worker) <= 55000
    for name, r in mine.items():
        assert 1124 <= r.start and r.stop <= 65536, (name, r)
    assert mine["sim"].stop <= EPHEMERAL_FLOOR
    assert mine["sim metrics"].stop <= EPHEMERAL_FLOOR
    for (a, ra), (b, rb) in itertools.combinations(mine.items(), 2):
        assert not _overlap(ra, rb), (worker, a, ra, b, rb)
    for other in WORKERS:
        if other == worker:
            continue
        for (a, ra), (b, rb) in itertools.product(
                mine.items(), _ranges(other).items()):
            assert not _overlap(ra, rb), (worker, a, ra, other, b, rb)


def test_a_base_already_set_wins():
    env = {"PYTEST_XDIST_WORKER": "gw3", "KFT_BASE_PORT": "40000"}
    testutil.claim_port_window(env)
    assert env["KFT_BASE_PORT"] == "40000"
    env = {}
    testutil.claim_port_window(env)
    assert env == {}


def test_the_sim_fleets_move_with_the_window():
    assert sim_runner.SIM_BASE_PORT == sim_runner._sim_base_port(
        hostspec.DEFAULT_WORKER_PORT)
    # a base with no room beneath it still gets ports that exist
    assert sim_runner._sim_base_port(1124) > 1124


def test_this_process_sits_in_its_window():
    """The conftest claimed the window before the library read the base."""
    if "PYTEST_XDIST_WORKER" in os.environ:
        assert os.environ.get("KFT_BASE_PORT")
    assert hostspec.DEFAULT_WORKER_PORT == knobs.get("KFT_BASE_PORT")
