"""Unit tests for kungfu_tpu.distributed (data-plane lifecycle helpers).

The process-level shutdown/re-init protocol itself is exercised end to
end by tests/test_elastic_distributed.py; these cover the pure parts.
"""
import numpy as np
import pytest

from kungfu_tpu import distributed as D
from kungfu_tpu.plan import PeerID


def test_coordinator_address_is_versioned():
    peers = ["127.0.0.1:31100", "127.0.0.1:31101"]
    a0 = D.coordinator_address(peers, 0)
    a1 = D.coordinator_address(peers, 1)
    a9 = D.coordinator_address(peers, 9)
    assert a0 == "127.0.0.1:32100"
    assert a1 == "127.0.0.1:32101"
    assert a9 == "127.0.0.1:32109"
    assert len({a0, a1, a9}) == 3  # distinct rendezvous per version


def test_coordinator_address_accepts_peerids():
    peers = [PeerID("10.0.0.1", 30000), PeerID("10.0.0.2", 30000)]
    assert D.coordinator_address(peers, 2) == "10.0.0.1:31002"


def test_coordinator_env_override_only_at_v0(monkeypatch):
    peers = ["127.0.0.1:31100"]
    monkeypatch.setenv("KFT_COORDINATOR", "10.1.2.3:9999")
    assert D.coordinator_address(peers, 0) == "10.1.2.3:9999"
    # a static address cannot follow elastic membership: later versions
    # fall back to the derived endpoint
    assert D.coordinator_address(peers, 1) == "127.0.0.1:32101"


def test_version_wraps_into_port_range():
    peers = ["127.0.0.1:31100"]
    # 20k consecutive versions get distinct rendezvous ports ...
    assert D.coordinator_address(peers, 1000) != \
        D.coordinator_address(peers, 0)
    assert D.coordinator_address(peers, 19999) != \
        D.coordinator_address(peers, 0)
    # ... then the space wraps (documented fencing window)
    assert D.coordinator_address(peers, 20000) == \
        D.coordinator_address(peers, 0)
    # a base port near the top of the range folds back into [1024, 65536)
    hi = ["127.0.0.1:60000"]
    for v in (0, 1, 9999):
        port = int(D.coordinator_address(hi, v).split(":")[1])
        assert 1024 <= port < 65536


def test_not_initialized_by_default():
    assert not D.is_initialized()
    assert D.version() is None
    D.shutdown()  # no-op when down
    assert not D.is_initialized()


def test_initialize_rejects_version_move_without_reinit(monkeypatch):
    # simulate a live plane; initialize() at another version must demand
    # an explicit reinit (the caller owns the teardown ordering)
    monkeypatch.setattr(D, "_live", (3, "127.0.0.1:32103", 2, 0))
    with pytest.raises(RuntimeError, match="reinit"):
        D.initialize(["127.0.0.1:31100", "127.0.0.1:31101"], 0, 4)
    # re-joining the SAME version is an idempotent no-op
    D.initialize(["127.0.0.1:31100", "127.0.0.1:31101"], 0, 3)


def test_broadcast_host_tree_singleton_passthrough():
    tree = {"a": np.arange(4, dtype=np.float32),
            "b": {"c": np.ones((2, 2), np.int32)}}
    out = D.broadcast_host_tree(tree, peer=None)
    assert np.array_equal(out["a"], tree["a"])
    assert np.array_equal(out["b"]["c"], tree["b"]["c"])


def test_colocated_workers_on_a_tpu_fail_with_a_message(monkeypatch):
    """A host's TPU chips belong to one process (libtpu): two workers on
    one host must fail with OUR message — both the one that got the
    chips and the one libtpu's lockfile turned away — while the CPU
    rig's colocated workers and one-worker-per-host pods pass."""
    import jax
    same = ["127.0.0.1:31100", "127.0.0.1:31101"]
    apart = [PeerID("10.0.0.1", 30000), PeerID("10.0.0.2", 30000)]
    D.require_own_chips(same, 0)                     # cpu backend: fine
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    D.require_own_chips(apart, 1)                    # own host: fine
    with pytest.raises(RuntimeError, match="one worker per host"):
        D.require_own_chips(same, 1)

    def lockfile():
        raise RuntimeError("Unable to initialize backend 'tpu': ABORTED: "
                           "libtpu multi-process lockfile")
    monkeypatch.setattr(jax, "default_backend", lockfile)
    with pytest.raises(RuntimeError, match="ROADMAP R2/S6") as ei:
        D.require_own_chips(same, 0)
    assert "lockfile" in str(ei.value.__cause__)
