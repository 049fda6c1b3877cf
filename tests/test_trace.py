"""Tracing/profiling (reference: TRACE_SCOPE + elastic _log_event)."""
import os

import numpy as np
import pytest

from kungfu_tpu.comm.session import Session
from kungfu_tpu.utils import trace


@pytest.fixture(autouse=True)
def _clean():
    trace.reset()
    yield
    trace.reset()
    os.environ.pop(trace.ENABLE_ENV, None)


def test_disabled_by_default():
    with trace.trace_scope("noop"):
        pass
    assert trace.scope_stats() == {}


def test_scopes_record_when_enabled():
    os.environ[trace.ENABLE_ENV] = "1"
    for _ in range(3):
        with trace.trace_scope("work"):
            pass
    stats = trace.scope_stats()
    assert stats["work"][0] == 3
    assert stats["work"][1] >= 0
    assert sorted(trace.scope_stats()) == ["work"]


def test_session_collectives_traced(devices):
    os.environ[trace.ENABLE_ENV] = "1"
    s = Session(mesh=None)
    x = np.ones((s.size, 4), np.float32)
    s.all_reduce(x, name="g0")
    s.all_reduce(x, name="g0")
    stats = trace.scope_stats()
    assert stats.get("kft::g0", (0, 0))[0] == 2


def test_events_always_on():
    t = trace.log_event("sync-begin")
    assert trace.events()[-1] == (t, "sync-begin")


def test_events_list_is_bounded():
    # always-on marks must not leak memory on a long-running worker
    assert trace.EVENTS_LIMIT > 0
    assert trace._events.maxlen == trace.EVENTS_LIMIT


def test_scope_records_duration_on_exception_path():
    """A scope that raises still accounts its duration, tagged as
    failed — losing the sample would hide exactly the
    slow-then-crashed cases (satellite fix: the accounting used to sit
    after the yield outside any finally)."""
    os.environ[trace.ENABLE_ENV] = "1"
    with pytest.raises(RuntimeError):
        with trace.trace_scope("doomed"):
            raise RuntimeError("boom")
    stats = trace.scope_stats()
    assert "doomed" not in stats          # success bucket untouched
    assert stats["doomed [failed]"][0] == 1
    assert stats["doomed [failed]"][1] >= 0
    # a later successful run of the same scope lands in its own bucket
    with trace.trace_scope("doomed"):
        pass
    stats = trace.scope_stats()
    assert stats["doomed"][0] == 1
    assert stats["doomed [failed]"][0] == 1


def test_resize_logs_events(devices):
    import jax.numpy as jnp
    import optax
    import kungfu_tpu.optimizers as kfopt
    from kungfu_tpu.elastic.trainer import ElasticTrainer

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] - y) ** 2)

    rng = np.random.RandomState(0)
    init = {"w": jnp.asarray(rng.randn(4, 2).astype(np.float32))}
    t = ElasticTrainer(loss_fn, lambda n: kfopt.synchronous_sgd(
        optax.sgd(0.1)), init, init_size=2)
    t.resize(4)
    names = [n for _, n in trace.events()]
    assert "resize-begin:2->4" in names
    assert "resize-end:4" in names
