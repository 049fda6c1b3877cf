"""Native C++ control-plane runtime tests.

Mirrors the reference's multi-node-without-a-cluster approach: N real
processes on 127.0.0.1 ports exercise the collectives against numpy as the
reference implementation (reference: scripts/tests/run-integration-tests.sh
sweeps strategies x np; tests/cpp/integration/fake_trainer.hpp checks
allreduce results exactly).
"""
import multiprocessing as mp
import os
import socket
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kungfu_tpu import native  # noqa: E402

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native lib unavailable")


def _free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _spawn(target, n, *extra):
    ports = _free_ports(n)
    peers = [f"127.0.0.1:{p}" for p in ports]
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [ctx.Process(target=target, args=(r, peers, q) + extra)
             for r in range(n)]
    for p in procs:
        p.start()
    results = {}
    try:
        for _ in range(n):
            r, val = q.get(timeout=120)
            if isinstance(val, str) and val.startswith("ERROR"):
                raise AssertionError(f"worker {r}: {val}")
            results[r] = val
        for p in procs:
            p.join(timeout=30)
            assert p.exitcode == 0
    finally:
        # ALWAYS reap: a worker hung in native code would otherwise be
        # joined forever by multiprocessing's atexit handler, turning a
        # failed hang-regression test into a hung pytest session
        for p in procs:
            if p.is_alive():
                p.terminate()
    return results


# ----------------------------------------------------------------- workers

def _w_allreduce(rank, peers, q, strategy):
    from kungfu_tpu.native import NativePeer
    try:
        with NativePeer(rank, peers) as p:
            rng = np.random.RandomState(7)  # same on all ranks
            base = rng.randn(4, len(peers), 1000).astype(np.float32)
            x = base[0, rank] * (rank + 1)
            contribs = [base[0, r] * (r + 1) for r in range(len(peers))]
            got = p.all_reduce(x, op="SUM", strategy=strategy, name="t")
            want = np.sum(contribs, axis=0)
            # reduction order differs per strategy → f32 associativity slack
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
            got = p.all_reduce(x, op="MAX", strategy=strategy, name="t2")
            np.testing.assert_array_equal(got, np.max(contribs, axis=0))
            ix = (np.arange(16, dtype=np.int64) + rank)
            got = p.all_reduce(ix, op="SUM", strategy=strategy, name="t3")
            want = np.sum([np.arange(16, dtype=np.int64) + r
                           for r in range(len(peers))], axis=0)
            np.testing.assert_array_equal(got, want)
            q.put((rank, "ok"))
    except Exception as e:  # pragma: no cover
        q.put((rank, f"ERROR {type(e).__name__}: {e}"))


def _w_suite(rank, peers, q):
    """broadcast / gather / allgather / consensus / barrier / tree / f16."""
    from kungfu_tpu.native import NativePeer
    try:
        n = len(peers)
        with NativePeer(rank, peers) as p:
            # broadcast from root 2 % n
            root = 2 % n
            x = (np.full(64, float(rank), np.float64) if rank == root
                 else np.zeros(64, np.float64))
            got = p.broadcast(x, root=root, name="b")
            np.testing.assert_array_equal(got, np.full(64, float(root)))
            # gather to root 0
            g = p.gather(np.full(3, rank, np.int32), root=0, name="g")
            if rank == 0:
                want = np.stack([np.full(3, r, np.int32) for r in range(n)])
                np.testing.assert_array_equal(g, want)
            # allgather
            ag = p.all_gather(np.full(2, rank * 10, np.int32), name="ag")
            want = np.stack([np.full(2, r * 10, np.int32) for r in range(n)])
            np.testing.assert_array_equal(ag, want)
            # consensus: identical then divergent
            assert p.consensus(b"same-bytes", name="c1") is True
            payload = b"diverged" if rank == n - 1 else b"same-one"
            assert p.consensus(payload, name="c2") is (n == 1)
            # explicit tree (star rooted at n-1)
            father = [n - 1] * n
            got = p.all_reduce_tree(np.full(8, rank + 1, np.float32), father,
                                    op="SUM", name="tree")
            np.testing.assert_allclose(got, np.full(8, n * (n + 1) / 2))
            # f16 ring
            h = np.full(1500, 0.5, np.float16)
            got = p.all_reduce(h, op="SUM", strategy="RING", name="h")
            np.testing.assert_allclose(got.astype(np.float32), 0.5 * n)
            p.barrier()
            q.put((rank, "ok"))
    except Exception as e:  # pragma: no cover
        q.put((rank, f"ERROR {type(e).__name__}: {e}"))


def _w_p2p(rank, peers, q):
    """versioned p2p store save/request + monitoring + ping."""
    from kungfu_tpu.native import NativePeer
    try:
        n = len(peers)
        with NativePeer(rank, peers) as p:
            model = np.arange(100, dtype=np.float32) + rank * 1000
            p.save("model", model, version=1)
            p.save("model", model + 1, version=2)
            p.barrier(name="saved")
            # request latest from the next peer (AD-PSGD pattern)
            target = (rank + 1) % n
            got = p.request(target, "model", model)
            np.testing.assert_allclose(
                got, np.arange(100, dtype=np.float32) + target * 1000 + 1)
            # versioned request
            got = p.request(target, "model", model, version=1)
            np.testing.assert_allclose(
                got, np.arange(100, dtype=np.float32) + target * 1000)
            # window GC: old versions beyond the window disappear
            p.barrier(name="requests-done")  # don't GC while peers still read
            for v in range(3, 8):
                p.save("model", model + v, version=v)
            p.barrier(name="gc")
            with pytest.raises(native.NativeError):
                p.request(target, "model", model, version=1)
            # an unversioned save must not pin versioned blobs (GC keeps
            # sliding even with the -1 slot present)
            p.save("pinned", model)  # unversioned
            for v in range(10, 16):
                p.save("pinned", model + v, version=v)
            p.barrier(name="gc2")
            with pytest.raises(native.NativeError):
                p.request(target, "pinned", model, version=10)
            got = p.request(target, "pinned", model, version=15)
            np.testing.assert_allclose(
                got, np.arange(100, dtype=np.float32) + target * 1000 + 15)
            # father-array validation
            with pytest.raises(ValueError):
                p.all_reduce_tree(model, [0] * (n + 1))
            # monitoring: egress counted, ping works
            assert p.egress_bytes() > 0
            rtt = p.ping(target)
            assert rtt >= 0.0
            lat = p.peer_latencies()
            assert len(lat) == n and lat[rank] == 0.0
            p.barrier(name="pre-exit")  # nobody tears down early
            q.put((rank, "ok"))
    except Exception as e:  # pragma: no cover
        q.put((rank, f"ERROR {type(e).__name__}: {e}"))


def _w_fence(rank, peers, q, healed):
    """Version-token fencing: peers on different tokens cannot talk
    (reference: connection.go:77-87).  Stale-token rejection is retried
    (token adoption is asynchronous during a resize), so rejection only
    surfaces after the retry budget; `healed` gates the heal phase so
    worker 1 doesn't burn its budget while worker 0 is still fenced."""
    from kungfu_tpu.native import NativePeer
    try:
        os.environ["KFT_CONN_RETRIES"] = "20"
        os.environ["KFT_CONN_RETRY_MS"] = "50"
        os.environ["KFT_RECV_TIMEOUT_S"] = "20"
        with NativePeer(rank, peers, token=rank) as p:  # mismatched tokens
            if rank == 0:
                try:
                    # broadcast from 0 dials peer 1 → stale-token reject
                    p.broadcast(np.ones(4, np.float32), root=0, name="x")
                    q.put((rank, "ERROR: fencing did not reject"))
                    return
                except native.NativeError:
                    pass
                # re-align on token 7 → cluster works again
                p.reset_connections(7)
                healed.set()
            else:
                assert healed.wait(timeout=60)
                p.reset_connections(7)
            p.barrier(name="fence-heal")
            q.put((rank, "ok"))
    except Exception as e:  # pragma: no cover
        q.put((rank, f"ERROR {type(e).__name__}: {e}"))


def _w_mst(rank, peers, q):
    """MST adaptation: measure latencies → all-gather → tree → allreduce."""
    from kungfu_tpu.native import NativePeer
    try:
        n = len(peers)
        with NativePeer(rank, peers) as p:
            father = p.mst_tree(root=0)
            assert len(father) == n and father[0] == 0
            got = p.all_reduce_tree(np.full(8, rank + 1, np.float32), father,
                                    op="SUM", name="mst-ar")
            np.testing.assert_allclose(got, np.full(8, n * (n + 1) / 2))
            p.barrier(name="pre-exit")
            q.put((rank, "ok"))
    except Exception as e:  # pragma: no cover
        q.put((rank, f"ERROR {type(e).__name__}: {e}"))


# ------------------------------------------------------------------- tests

@pytest.mark.parametrize("strategy", ["STAR", "MULTI_STAR", "RING", "CLIQUE",
                                      "TREE", "BINARY_TREE",
                                      "BINARY_TREE_STAR",
                                      "MULTI_BINARY_TREE_STAR", "AUTO"])
@pytest.mark.parametrize("n", [1, 2, 4])
def test_allreduce_strategies(strategy, n):
    if n == 1 and strategy != "AUTO":
        pytest.skip("n=1 covered once via AUTO")
    _spawn(_w_allreduce, n, strategy)


def test_collective_suite():
    _spawn(_w_suite, 4)


def test_collective_suite_np3():
    _spawn(_w_suite, 3)


def test_p2p_store_and_monitoring():
    _spawn(_w_p2p, 3)


def test_token_fencing():
    healed = mp.get_context("spawn").Event()
    _spawn(_w_fence, 2, healed)


def test_single_peer_degenerate():
    _spawn(_w_suite, 1)


def test_mst_adaptation():
    _spawn(_w_mst, 4)


def _w_async_pair_avg(rank, peers, q, selection):
    """TRUE-async AD-PSGD: local SGD on a shared quadratic + store-based
    pair averaging (reference: PairAveragingOptimizer over the Go store)."""
    from kungfu_tpu.native import NativePeer
    from kungfu_tpu.optimizers import AsyncPairAverager
    try:
        n = len(peers)
        with NativePeer(rank, peers) as p:
            import jax.numpy as jnp
            target = jnp.asarray([3.0, -2.0, 1.0, 4.0])
            # divergent inits: averaging must pull them together
            params = {"w": jnp.full(4, float(rank * 10))}
            avg = AsyncPairAverager(p, selection=selection)
            avg.save(params)
            p.barrier(name="init")  # reference: step-0 store init barrier
            # What a rank mixes in is whatever its peer last saved, and
            # how old that is was up to the machine: a rank that took its
            # sixty steps while a peer had not yet taken one ended on that
            # peer's initial model.  AD-PSGD converges under a BOUNDED
            # delay, so the ranks meet every ten steps (exchanges inside a
            # window stay unsynchronised) and train until all of them are
            # there: six windows as before, more only while one is behind.
            for window in range(30):
                for _ in range(10):
                    params = avg.mix(params)
                    grad = {"w": 2.0 * (params["w"] - target)}
                    params = {"w": params["w"] - 0.1 * grad["w"]}
                    avg.save(params)
                err = float(jnp.abs(params["w"] - target).max())
                worst = p.all_reduce(np.asarray([err], np.float32),
                                     op="MAX", name=f"err{window}")
                if window >= 5 and worst[0] < 0.5:
                    break
            assert err < 0.5, f"rank {rank} err {err}"
            q.put((rank, "ok"))
    except Exception as e:  # pragma: no cover
        q.put((rank, f"ERROR {type(e).__name__}: {e}"))


@pytest.mark.parametrize("selection", ["random", "roundrobin"])
def test_async_pair_averaging(selection):
    _spawn(_w_async_pair_avg, 3, selection)


def test_allreduce_tcp_only_fallback(monkeypatch):
    """KFT_CONFIG_USE_UNIX=0 forces colocated peers onto TCP (the
    cross-host path); results must be identical to the default unix-socket
    transport (reference: UseUnixSock toggle, config.go:11-19)."""
    monkeypatch.setenv("KFT_CONFIG_USE_UNIX", "0")
    _spawn(_w_allreduce, 3, "RING")


def _w_unix_listener(rank, peers, q):
    from kungfu_tpu.native import NativePeer
    try:
        with NativePeer(rank, peers) as p:
            host, port = peers[rank].rsplit(":", 1)
            with open("/proc/net/unix") as f:
                names = f.read()
            # abstract name carries host AND port so loopback-alias
            # "hosts" can reuse ports on one machine
            assert f"@kft-{host}-{port}" in names, "unix listener missing"
            p.barrier()
            q.put((rank, "ok"))
    except Exception as e:  # pragma: no cover
        q.put((rank, f"ERROR {type(e).__name__}: {e}"))


def test_unix_listener_present(monkeypatch):
    """Default transport registers the abstract unix socket."""
    monkeypatch.setenv("KFT_CONFIG_USE_UNIX", "1")  # isolate from ambient
    _spawn(_w_unix_listener, 2)


def _f16_rounding_worker(rank, peers, q):
    try:
        with native.NativePeer(rank, peers) as p:
            # 1.0 + 2.0009765625 needs f16 mantissa rounding; 11 elements
            # exercise the SIMD body (0..7) AND the scalar tail (8..10)
            x = np.full(11, 1.0 if rank == 0 else np.float16(2.0009765625),
                        np.float16)
            got = p.all_reduce(x, op="SUM", name="f16rne")
            q.put((rank, got.view(np.uint16).tolist()))
    except Exception as e:  # pragma: no cover
        q.put((rank, f"ERROR {e!r}"))


def test_f16_reduce_simd_tail_bit_identical():
    """SIMD body (elements 0..7) and scalar tail (8..10) of the f16
    reduce must produce IDENTICAL bits — both round to nearest-even, so
    the result cannot depend on element index or host ISA (bit-exact
    consensus relies on this)."""
    results = _spawn(_f16_rounding_worker, 2)
    for bits in results.values():
        assert len(set(bits)) == 1, bits
    assert results[0] == results[1]


def _w_dead_peer(rank, peers, q):
    import os
    import time
    os.environ["KFT_RECV_TIMEOUT_S"] = "3"
    os.environ["KFT_CONN_RETRIES"] = "10"  # dead-peer dials give up in ~2s
    from kungfu_tpu.native import NativeError, NativePeer
    try:
        with NativePeer(rank, peers) as p:
            p.barrier(name="up")
            if rank == 2:
                q.put((rank, "ok"))  # simulate a crash: vanish mid-job
                q.close()
                q.join_thread()  # flush the feeder BEFORE the hard exit
                os._exit(0)
            t0 = time.time()
            try:
                p.all_reduce(np.ones(4, np.float32), name="doomed")
                q.put((rank, "ERROR collective succeeded without peer 2"))
                return
            except NativeError:
                pass
            dt = time.time() - t0
            # fail FAST and CLEANLY: bounded by the configured recv
            # timeout (+ margin), never a hang
            assert dt < 30, dt
            q.put((rank, "ok"))
    except Exception as e:  # pragma: no cover
        q.put((rank, f"ERROR {type(e).__name__}: {e}"))


def test_dead_peer_fails_collectives_cleanly():
    """Failure detection (SURVEY §5): when a peer dies, survivors' next
    collective raises NativeError within the configured receive timeout
    instead of hanging (reference: bounded conn retries + recv deadlines,
    config.go:16-19)."""
    _spawn(_w_dead_peer, 3)


def _w_stall(rank, peers, q):
    import os
    import time
    from kungfu_tpu.native import NativePeer
    try:
        with NativePeer(rank, peers) as p:
            p.set_stall_threshold(1.0)
            p.barrier(name="up")
            if rank == 1:
                time.sleep(4)  # make rank 0's collective pend > threshold
                p.all_reduce(np.ones(2, np.float32), name="slow")
                q.put((rank, "ok"))
                return
            # capture the C++ runtime's stderr (fd 2): the stall report
            # is an fprintf from the service thread
            cap = os.path.join(os.environ["STALL_OUT"], f"err.{rank}")
            fd = os.open(cap, os.O_CREAT | os.O_WRONLY | os.O_TRUNC, 0o644)
            saved = os.dup(2)
            os.dup2(fd, 2)
            try:
                p.all_reduce(np.ones(2, np.float32), name="slow")
                time.sleep(0.5)
            finally:
                os.dup2(saved, 2)
                os.close(fd)
                os.close(saved)
            with open(cap) as f:
                text = f.read()
            q.put((rank, "ok" if "STALL" in text else
                   f"ERROR no stall report in: {text!r}"))
    except Exception as e:  # pragma: no cover
        q.put((rank, f"ERROR {type(e).__name__}: {e}"))


def test_stall_detector_reports_pending_op(tmp_path, monkeypatch):
    """An op pending past the stall threshold is reported by the service
    loop while it is still in flight (reference: InstallStallDetector,
    libkungfu-comm/main.go:165-175, gated KUNGFU_CONFIG_ENABLE_STALL_
    DETECTION — here kft_set_stall_threshold / KFT_CONFIG_ENABLE_STALL_
    DETECTION)."""
    monkeypatch.setenv("STALL_OUT", str(tmp_path))
    _spawn(_w_stall, 2)
