"""Host→device prefetch pipeline: correctness, overlap, error paths."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kungfu_tpu.comm.mesh import flat_mesh
from kungfu_tpu.data.pipeline import Prefetcher, prefetch_to_mesh


def test_prefetcher_yields_all_batches_in_order():
    batches = [{"x": np.full((4, 2), i), "y": np.arange(4) + i}
               for i in range(7)]
    with Prefetcher(iter(batches), depth=3) as pf:
        got = list(pf)
    assert len(got) == 7
    for i, b in enumerate(got):
        assert isinstance(b["x"], jax.Array)
        np.testing.assert_array_equal(np.asarray(b["x"]),
                                      batches[i]["x"])
        np.testing.assert_array_equal(np.asarray(b["y"]),
                                      batches[i]["y"])


def test_prefetcher_overlaps_slow_source():
    """A source that takes s seconds per batch and a consumer that takes
    c per step finish in ~max(s, c)*n, not (s+c)*n, once the pipeline
    is primed."""
    n, s, c = 6, 0.08, 0.08

    def slow_source():
        for i in range(n):
            time.sleep(s)
            yield np.full((2,), i)

    t0 = time.perf_counter()
    with Prefetcher(slow_source(), depth=2) as pf:
        for _ in pf:
            time.sleep(c)
    overlapped = time.perf_counter() - t0
    serial_floor = n * (s + c)
    # generous margin for a loaded machine: must beat fully-serial by
    # a clear fraction of the theoretical saving
    assert overlapped < serial_floor - 0.6 * min(s, c) * (n - 1), \
        (overlapped, serial_floor)


def test_prefetcher_surfaces_source_exception():
    def bad_source():
        yield np.zeros(2)
        raise RuntimeError("disk on fire")

    pf = Prefetcher(bad_source(), depth=2)
    next(pf)
    with pytest.raises(RuntimeError, match="disk on fire"):
        next(pf)
    pf.close()


def test_prefetcher_exhaustion_is_latched():
    """next() after StopIteration, after a surfaced error, or after
    close() raises immediately instead of blocking forever."""
    pf = Prefetcher(iter([np.zeros(2)]), depth=2)
    assert len(list(pf)) == 1                 # drains the stream
    assert list(pf) == []                     # second loop: empty, no hang
    with pytest.raises(StopIteration):
        next(pf)

    def bad():
        raise RuntimeError("boom")
        yield                                  # pragma: no cover

    pf2 = Prefetcher(bad(), depth=1)
    for _ in range(2):                         # error re-raised, no hang
        with pytest.raises(RuntimeError, match="boom"):
            next(pf2)

    pf3 = Prefetcher(iter([np.zeros(2)] * 5), depth=1)
    next(pf3)
    pf3.close()
    with pytest.raises(StopIteration):
        next(pf3)


def test_prefetcher_close_mid_stream():
    """Early exit doesn't deadlock on a blocked producer."""
    def endless():
        i = 0
        while True:
            yield np.full((2,), i)
            i += 1

    pf = Prefetcher(endless(), depth=1)
    next(pf)
    pf.close()            # must return promptly
    assert not pf._thread.is_alive()


def test_prefetch_to_mesh_shards_batch_axis(devices):
    mesh = flat_mesh(devices[:4])
    batches = [(np.arange(8 * 3, dtype=np.float32).reshape(8, 3)
                + 100 * i,
                np.arange(8) + i) for i in range(3)]
    with prefetch_to_mesh(iter(batches), mesh, depth=2) as pf:
        got = list(pf)
    assert len(got) == 3
    for i, (bx, by) in enumerate(got):
        np.testing.assert_array_equal(np.asarray(bx), batches[i][0])
        # leading axis sharded over the mesh: 4 shards of 2 rows
        assert len(bx.sharding.device_set) == 4
        shard_rows = {s.data.shape[0] for s in bx.addressable_shards}
        assert shard_rows == {2}


def test_prefetch_feeds_train_step(devices):
    """The staged layout is consumed by build_train_step without any
    re-layout errors, and training progresses."""
    import optax

    import kungfu_tpu.optimizers as kfopt
    from kungfu_tpu.training import (build_train_step, init_opt_state,
                                     replicate)

    mesh = flat_mesh(devices[:4])
    params = {"w": jnp.zeros((3, 2))}

    def loss_fn(p, batch):
        bx, by = batch
        return jnp.mean((bx @ p["w"] - by) ** 2)

    opt = kfopt.synchronous_sgd(optax.sgd(0.1))
    sp = replicate(params, mesh)
    st = init_opt_state(opt, sp, mesh)
    step = build_train_step(loss_fn, opt, mesh)

    rng = np.random.RandomState(0)
    W = rng.randn(3, 2).astype(np.float32)
    batches = []
    for _ in range(5):
        bx = rng.randn(8, 3).astype(np.float32)
        batches.append((bx, bx @ W))
    losses = []
    with prefetch_to_mesh(iter(batches), mesh, depth=2) as pf:
        for batch in pf:
            sp, st, loss = step(sp, st, batch)
            losses.append(float(np.asarray(loss)[0]))
    assert len(losses) == 5
    assert losses[-1] < losses[0]


@pytest.fixture
def counter():
    """A fresh current counter, so the feed's records land in it."""
    from kungfu_tpu.utils.compile_cache import CompileCounter
    return CompileCounter()


def _feed_records(counter):
    from kungfu_tpu.utils.compile_cache import CompileCounter
    stage = {r.seq: r for r in counter.host if r.kind == CompileCounter.STAGE}
    handout = [r for r in counter.host if r.kind == CompileCounter.HANDOUT]
    return stage, handout


@pytest.mark.parametrize("slow", ["source", "consumer"])
def test_each_batch_is_staged_and_handed_out_under_one_number(counter, slow):
    """A slow source shows as the feed's staging and the consumer's wait;
    a slow consumer as staging that is quick and a queue found full. The
    sequence number joins each batch's two records."""
    n, pause, depth = 5, 0.05, 2

    def source():
        for i in range(n):
            if slow == "source":
                time.sleep(pause)
            yield np.full((2,), i)

    with Prefetcher(source(), depth=depth) as pf:
        for _ in pf:
            if slow == "consumer":
                time.sleep(pause)
    stage, handout = _feed_records(counter)
    assert len(handout) == n and set(stage) == {r.seq for r in handout}
    assert [r.seq for r in handout] == sorted(r.seq for r in handout)
    for r in handout:
        assert stage[r.seq].end_ns <= r.end_ns      # staged before handed
        assert r.start_ns <= r.end_ns and 0 <= r.value <= depth
    waits = [(r.end_ns - r.start_ns) / 1e9 for r in handout]
    staged = [(r.end_ns - r.start_ns) / 1e9 for r in stage.values()]
    if slow == "source":
        assert min(staged) >= 0.9 * pause
        assert min(waits[1:]) >= 0.5 * pause        # waited for each batch
    else:
        assert max(staged) < pause and max(waits[1:]) < pause
        assert handout[2].value == depth            # the queue was full


def test_the_feeds_records_are_bounded_and_outlive_the_prefetcher(
        monkeypatch):
    from kungfu_tpu.utils.compile_cache import CompileCounter
    monkeypatch.setattr(CompileCounter, "MAX_RECORDS", 4)
    counter = CompileCounter()
    pf = Prefetcher(iter([np.zeros(2)] * 6), depth=2)
    assert len(list(pf)) == 6
    pf.close()
    del pf
    # the newest four, the last batch's hand-out last
    assert len(counter.host) == 4
    assert counter.host[-1].kind == CompileCounter.HANDOUT
    assert counter.host[-1].seq == max(r.seq for r in counter.host)


def test_no_counter_no_records(monkeypatch):
    from kungfu_tpu.utils import compile_cache
    counter = compile_cache.CompileCounter()
    monkeypatch.setattr(compile_cache, "_current", None)
    with Prefetcher(iter([np.zeros(2)] * 3), depth=2) as pf:
        assert len(list(pf)) == 3
    assert _feed_records(counter) == ({}, [])
