"""Spans of the benchmark's own loop: name, start and end on the host's
monotonic clock, kept in memory. `on_profile_clock` lays them on a profiler
trace's clock, so that device gaps can be held against them."""
from __future__ import annotations

import contextlib
import time


def clock_offset_ns() -> int:
    """What to add to `perf_counter_ns` to get the time of day in ns, which
    is the clock a profiler trace's `profile_start_time` is on."""
    return time.time_ns() - time.perf_counter_ns()


class Spans:
    def __init__(self):
        self.events = []            # (name, start_ns, end_ns)

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            self.events.append((name, t0, time.perf_counter_ns()))

    def total_ns(self, name: str, since_ns: int = 0, until_ns=None) -> int:
        return sum(e - s for n, s, e in self.events
                   if n == name and s >= since_ns
                   and (until_ns is None or e <= until_ns))

    def on_profile_clock(self, offset_ns: int, profile_start_ns: int,
                         since_ns: int = 0) -> list:
        """(name, start_ns, duration_ns) of the spans that began at or after
        `since_ns`, with starts counted from the profile's start as a
        trace's events are."""
        return [(n, s + offset_ns - profile_start_ns, e - s)
                for n, s, e in self.events if s >= since_ns]
