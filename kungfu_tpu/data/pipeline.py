"""Host→device input pipeline: background staging with device prefetch.

The reference leans on tf.data for input pipelining (its helpers wrap
TF dataset iterators); the TPU-native equivalent is explicit
double-buffering: while the compiled step crunches batch *i*, a
background thread is already H2D-transferring batch *i+1* (and the
host source — decode/augment/shard — runs ahead of that by ``depth``).
On a TPU the transfer rides DMA and overlaps compute for free once the
arrays are on their way; what must NOT happen is the step blocking on
``np.asarray`` conversion + transfer *after* the previous step
finishes, which serialises host time into the step time.

Two pieces:

- :class:`Prefetcher` — wraps any iterator of (pytrees of) numpy
  batches; a worker thread pulls from the source, places each leaf on
  device (optionally sharded over a mesh), and keeps ``depth`` staged
  batches ready.  Exceptions from the source surface at the consuming
  ``next()``; close() joins the worker.
- :func:`prefetch_to_mesh` — convenience: stage with
  ``jax.device_put(x, NamedSharding(mesh, P('peers', ...)))`` so the
  leading batch axis lands pre-sharded over the data-parallel mesh the
  training step consumes (no per-step re-layout).

Works with :class:`kungfu_tpu.elastic.dataset.ElasticDataShard` — the
shard decides WHICH samples; this pipeline hides WHEN they move.

While a :class:`~kungfu_tpu.utils.compile_cache.CompileCounter` is
current, each batch leaves two records there, joined by one sequence
number: ``feed.stage`` (the worker's source ``next`` and placement) and
``feed.handout`` (the consumer's wait inside ``next()``, ending at the
hand-out, with the queue's depth it found).  They live in the counter,
so they outlast the prefetcher.
"""
from __future__ import annotations

import itertools
import queue
import threading
import time
from typing import Callable, Iterator, Optional

import jax
import numpy as np

from ..utils.compile_cache import CompileCounter, current_counter

_SENTINEL = object()
# one numbering for every prefetcher of the process, so a sequence number
# names one batch among all of a counter's records
_SEQ = itertools.count()


class Prefetcher:
    """Iterator adaptor: stages ``depth`` device-resident batches ahead.

    ``place`` maps a host batch (pytree of numpy arrays) to its
    device-resident form; default ``jax.device_put`` on the default
    device.  The worker thread runs the SOURCE and the placement, so
    per-batch host work (decode, augment, conversion, H2D enqueue)
    overlaps the previous step's device time.
    """

    def __init__(self, source: Iterator, depth: int = 2,
                 place: Optional[Callable] = None):
        if depth < 1:
            raise ValueError("depth must be >= 1")
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._place = place or jax.device_put
        self._src = source
        self._err: Optional[BaseException] = None
        self._done = False          # latched: stream ended or closed
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        try:
            source = iter(self._src)
            while True:
                began = time.perf_counter_ns()
                try:
                    batch = next(source)
                except StopIteration:
                    break
                if self._stop.is_set():
                    return
                seq = next(_SEQ)
                staged = (seq, jax.tree_util.tree_map(self._place, batch))
                counter = current_counter()
                if counter is not None:
                    counter.add(CompileCounter.STAGE, "", began,
                                time.perf_counter_ns(), seq)
                while not self._stop.is_set():
                    try:
                        self._q.put(staged, timeout=0.1)
                        break
                    except queue.Full:
                        continue
        except BaseException as e:        # surfaced at the consumer
            self._err = e
        finally:
            while not self._stop.is_set():
                try:
                    self._q.put(_SENTINEL, timeout=0.1)
                    return
                except queue.Full:
                    continue

    def __iter__(self):
        return self

    def __next__(self):
        # latched end state: a second loop / a retry after the surfaced
        # source error / a next() after close() must not block forever
        # on the consumed one-shot sentinel
        if self._done:
            if self._err is not None:
                raise self._err
            raise StopIteration
        began, depth = time.perf_counter_ns(), self._q.qsize()
        item = self._q.get()
        if item is _SENTINEL:
            self._done = True
            if self._err is not None:
                raise self._err
            raise StopIteration
        seq, batch = item
        counter = current_counter()
        if counter is not None:
            counter.add(CompileCounter.HANDOUT, "", began,
                        time.perf_counter_ns(), seq, depth)
        return batch

    def close(self):
        """Stop the worker (used on early exit; idempotent)."""
        self._done = True
        self._stop.set()
        # drain so a blocked put wakes up
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def prefetch_to_mesh(source: Iterator, mesh, depth: int = 2,
                     batch_axis_name: Optional[str] = None) -> Prefetcher:
    """Prefetch with each leaf pre-sharded over ``mesh``: the leading
    (batch) axis is split across every mesh axis (the layout
    ``training.build_train_step`` consumes), so the step never re-lays
    out its inputs.  ``batch_axis_name`` overrides which mesh axis
    shards the batch (default: all of them, in order)."""
    from jax.sharding import NamedSharding, PartitionSpec

    axes = ((batch_axis_name,) if batch_axis_name
            else tuple(mesh.axis_names))
    spec = PartitionSpec(axes)

    def place(x):
        x = np.asarray(x)
        return jax.device_put(x, NamedSharding(mesh, spec))

    return Prefetcher(source, depth=depth, place=place)
