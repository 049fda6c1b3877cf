"""Shared test helpers (pytest adds tests/ to sys.path: `import testutil`)."""
import numpy as np

# The ports of test processes that run side by side (xdist workers, the
# shards of tools/ci.sh).  A process places everything it binds from ONE
# base, KFT_BASE_PORT (plan/hostspec._base_port), and this is the one
# place that hands the bases out.  Under base b a process may bind
#
#   b - 100                    its runner            (DEFAULT_RUNNER_PORT)
#   b        .. b + 99         its workers; test_multihost_launcher's two
#                              clusters are twenty each from b + 60
#   b + 1000 .. b + 1099       its jax.distributed coordinators: peer 0's
#                              port + 1000 + the cluster version
#   b + 200  .. b + 799        its sim fleets' metrics servers
#   b - 9800 .. b - 9201       its sim fleets' workers (sim/runner.py:
#                              SIM_PORT_OFFSET, SIM_PORTS)
#   b + 10000 .. b + 10099     its workers' metrics  (MONITOR_PORT_OFFSET)
#
# Windows are 900 apart, so the coordinators of one fall into b + 100 ..
# b + 199 of the next, which nothing else uses: a window is b - 100 ..
# b + 799 with one image 9,800 below and one 10,000 above.  Eight of
# them from 22300 lie in 22200 .. 29699 (the last one's coordinators
# included), their sim images in 12500 .. 19399 and their monitor images
# in 32300 .. 38699: pairwise disjoint, clear of everything the default
# base 31100 owns (21300 .. 21899, 31000 .. 31899, 32100 .. 32199, 41100
# .. 41199) so that a run without xdist beside them is safe too, inside
# _base_port's 1124 .. 55000, and every sim port under the ephemeral
# floor 32768.  tests/test_port_windows.py holds the layout to this.
PORT_WINDOWS = 8
WORKER_PORTS = 100
_FIRST_WINDOW = 22300
_WINDOW_STRIDE = 900


def window_base_port(worker: str) -> int:
    """KFT_BASE_PORT of xdist worker ``gw<k>`` (a ci.sh shard ``s`` asks
    as ``gw<s>``).  Past eight the windows are shared again, as all of
    them were before."""
    k = int(worker.removeprefix("gw")) % PORT_WINDOWS
    return _FIRST_WINDOW + k * _WINDOW_STRIDE


def claim_port_window(env) -> None:
    """Give an xdist worker its window (tests/conftest.py, before the
    first import of kungfu_tpu: plan/hostspec reads the base once, and
    the processes a test starts inherit it).  A base already set wins;
    without xdist nothing changes."""
    worker = env.get("PYTEST_XDIST_WORKER")
    if worker and not env.get("KFT_BASE_PORT"):
        env["KFT_BASE_PORT"] = str(window_base_port(worker))


def data_plane_supported() -> bool:
    """True when this jax build can run a GLOBAL computation spanning two
    OS processes on the CPU backend (the substrate of every multi-process
    trainer test: DistributedElasticTrainer, ShardedElasticTrainer, the
    chaos scenario matrix).  Older jaxlib CPU backends reject it with
    "Multiprocess computations aren't implemented" — those tests must
    SKIP there, not fail.  One probe implementation, shared with the
    chaos scenario runner (which self-skips off the same answer);
    override with KFT_TESTS_DATA_PLANE=0/1 to skip the probe."""
    from kungfu_tpu.chaos.runner import data_plane_supported as probe
    return probe()


def tree_allclose(a, b, rtol=2e-4, atol=2e-5):
    """Assert two pytrees match leaf-for-leaf within tolerance."""
    import jax
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb), (len(fa), len(fb))
    for x, y in zip(fa, fb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


def peers_on(hosts):
    """PeerList from [(host, slots), ...] (shared by plan/property tests)."""
    from kungfu_tpu.plan import PeerID, PeerList
    ps = []
    for h, k in hosts:
        for s in range(k):
            ps.append(PeerID(h, 31100 + s, s))
    return PeerList(ps)
