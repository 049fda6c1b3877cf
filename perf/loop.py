"""The closed training loop every training cell runs.

Set-up builds one object, the compiled step with its state, and drives it
from the seed through its first steps, by the same call and the same feed as
the window. Those steps are what `correct` compares; the same object then
goes on into the window. A rate is all the work of the window over all of
its time: the window opens when the set-up's last step is done and closes in
`block_until_ready` on the last step's outputs.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import shutil
import time

import jax
import numpy as np

from perf import program, trace as tracing
from perf.spans import Spans, clock_offset_ns

AHEAD = 2          # the host fetches the loss of step i - AHEAD


@dataclasses.dataclass
class Outcome:
    setup_s: float
    window_s: float
    steps: int
    rate: float                 # units per second over the whole window
    losses_finite: bool
    first_steps: dict           # what `correct` compares, program side
    compiled_in_window: int
    spans: Spans
    window_ns: tuple
    trace: object = None        # perf.trace.Trace of the traced stretch
    setup_marks: dict = None    # seconds since the start at set-up's stages


def run(job: program.Job, key, prefetcher, seconds: float,
        started: float, reference_steps: int, trace_steps: int = 0,
        trace_dir: str = "", counter=None) -> Outcome:
    """Drive `job` from `key`. `prefetcher` yields the device batches;
    `started` is the process's start on `perf_counter`."""
    spans = Spans()
    marks = {"built": time.perf_counter() - started}
    state = job.init_state(key)
    jax.block_until_ready(state)
    marks["state_made"] = time.perf_counter() - started

    def one_step(state):
        with spans.span("input_wait"):
            batch = next(prefetcher)
        with spans.span("dispatch"):
            return job.step(state, batch)

    # the first steps: compiled or loaded by the first call, compared later
    first = {"losses": []}
    has_mstate = len(state) > 2
    if job.probes is None:      # compiled once for all the seeds of a process
        job.probes = (
            program.change_norms(
                lambda k: job.ref_family.init_params(k, job.config)),
            program.change_norms(
                lambda k: job.ref_family.init_model_state(job.config)))
    change, state_change = job.probes
    for i in range(1, reference_steps + 1):
        state, loss = one_step(state)
        first["losses"].append(loss)
        if i == 1:
            first["grad_norms"] = program.first_grad_norms(state[1],
                                                           job.optimizer)
    jax.block_until_ready(state)
    marks["first_steps"] = time.perf_counter() - started
    # read before the next step donates the state away
    first["change_norms"] = change(key, state[0])
    first["state_norms"] = (state_change(key, state[2]) if has_mstate
                            else np.zeros((0,)))
    first = jax.tree_util.tree_map(
        lambda x: np.asarray(x, np.float64).reshape(-1), first)
    first["losses"] = [float(x[0]) for x in first["losses"]]
    jax.block_until_ready(state)

    pending = collections.deque()
    fetched = []

    def advance(state):
        state, loss = one_step(state)
        pending.append(loss)
        if len(pending) > AHEAD:
            with spans.span("loss_fetch"):
                fetched.append(float(np.asarray(pending.popleft())[0]))
        return state

    def drain(state):
        jax.block_until_ready((state, list(pending)))
        done = time.perf_counter(), time.perf_counter_ns()
        fetched.extend(float(np.asarray(x)[0]) for x in pending)
        pending.clear()
        return done

    compiled_before = counter.compiled if counter else 0
    t_open, open_ns = time.perf_counter(), time.perf_counter_ns()
    steps = 0
    while time.perf_counter() - t_open < seconds:
        state = advance(state)
        steps += 1
    t_close, close_ns = drain(state)
    compiled = (counter.compiled if counter else 0) - compiled_before

    traced = None
    if trace_steps:
        # the same loop goes on, now under the profiler. Device events only:
        # with the host tracer on, the runtime's transfer threads write
        # millions of events for the image batches (0.9 GB and minutes for
        # 30 steps) and the device stalls while they are drained. The
        # loop's own spans are laid on the trace's clock instead.
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        offset_ns, since_ns = clock_offset_ns(), time.perf_counter_ns()
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            for _ in range(trace_steps):
                state = advance(state)
            drain(state)
        finally:
            jax.profiler.stop_trace()
        traced = tracing.load(tracing.find_xplane(trace_dir))
        if traced.profile_start_ns:
            traced.host_spans = spans.on_profile_clock(
                offset_ns, traced.profile_start_ns, since_ns)

    del state
    return Outcome(
        setup_s=t_open - started, window_s=t_close - t_open, steps=steps,
        rate=steps * job.units_per_step / (t_close - t_open),
        losses_finite=bool(np.all(np.isfinite(fetched))),
        first_steps=first, compiled_in_window=compiled, spans=spans,
        window_ns=(open_ns, close_ns), trace=traced, setup_marks=marks)
