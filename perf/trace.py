"""From a profiler trace to numbers: the device's busy time as the union of
its operations' intervals, the idle share, a kernel's summed time, the
longest gaps and what the host was doing in each.

The arithmetic works on plain lists of (name, start_ns, duration_ns), so it
is checked on hand-built lists; `load` turns an `.xplane.pb` into them with
`jax.profiler.ProfileData`.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import List, Tuple

Event = Tuple[str, int, int]            # name, start_ns, duration_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"                    # one event per operation that ran
MODULES_LINE = "XLA Modules"            # one event per program execution
ENVIRONMENT_PLANE = "Task Environment"  # holds `profile_start_time`


@dataclasses.dataclass
class Trace:
    ops: List[List[Event]]              # per device
    modules: List[List[Event]]          # per device
    host_spans: List[Event]             # the benchmark loop's own spans
    # a device operation's whole text in the trace (its HLO line), by its
    # short name: where a kernel can only be told by its call target
    text: dict = dataclasses.field(default_factory=dict)
    # when the profile began, in ns of the time of day: every event's start
    # counts from here
    profile_start_ns: int = 0

    def window(self) -> Tuple[int, int]:
        """From the first to the last moment a traced device ran a program
        of the window: whole executions of the step."""
        evs = [e for dev in (self.modules if any(self.modules) else self.ops)
               for e in dev]
        return (min(s for _, s, _ in evs), max(s + d for _, s, d in evs))


def merge(intervals) -> List[Tuple[int, int]]:
    """Union of (start, end) intervals, sorted and disjoint."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_ns(events: List[Event], window: Tuple[int, int]) -> int:
    """Time inside `window` during which at least one event ran."""
    lo, hi = window
    return sum(e - s for s, e in merge(
        (max(s, lo), min(s + d, hi)) for _, s, d in events
        if s < hi and s + d > lo))


def idle_share(events: List[Event], window: Tuple[int, int]) -> float:
    return 1.0 - busy_ns(events, window) / (window[1] - window[0])


def summed(events: List[Event]):
    """Total duration per name, largest first."""
    totals: dict = {}
    for name, _, d in events:
        totals[name] = totals.get(name, 0) + d
    return sorted(totals.items(), key=lambda kv: -kv[1])


def kernel_ns(trace: "Trace", pattern: str) -> int:
    """Summed device time, on the first device and inside the trace's
    window, of the operations in whose whole text (the HLO line, where the
    trace gives one) `pattern` is found: a kernel without a name of its own
    is told by its call target and shapes."""
    lo, hi = trace.window()
    rx = re.compile(pattern)
    return sum(d for name, s, d in trace.ops[0]
               if s >= lo and s + d <= hi
               and rx.search(trace.text.get(name, name)))


def gaps(events: List[Event], window: Tuple[int, int]):
    """The intervals of `window` in which nothing ran, longest first."""
    lo, hi = window
    busy = merge((max(s, lo), min(s + d, hi)) for _, s, d in events
                 if s < hi and s + d > lo)
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return sorted(out, key=lambda g: g[0] - g[1])


def attribute(gap: Tuple[int, int], spans: List[Event]) -> str:
    """The host span that covers most of the gap, or `none`."""
    best, best_cover = "none", 0
    for name, s, d in spans:
        cover = min(gap[1], s + d) - max(gap[0], s)
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def short(name: str) -> str:
    """The profiler names a device operation by its whole HLO text,
    `%fusion.12 = bf16[...] fusion(...)`: keep what stands before the `=`."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(events: List[Event]) -> List[Event]:
    """Each event with the time its children cover taken out of it. On a
    device line a `while` or a `call` encloses the operations of its body,
    and would count their time a second time."""
    out: List[list] = []
    stack: List[int] = []                      # indices into `out`
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= out[stack[-1]][3]:
            stack.pop()
        if stack:
            out[stack[-1]][2] -= d
        out.append([name, s, d, s + d])
        stack.append(len(out) - 1)
    return [(name, s, max(d, 0)) for name, s, d, _ in out]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, modules, text, began = {}, {}, {}, 0
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if plane.name == ENVIRONMENT_PLANE:
            began = int(dict(plane.stats).get("profile_start_time", 0))
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                into = (ops if line.name == OPS_LINE else modules).setdefault(
                    int(m.group(1)), [])
                for ev in line.events:
                    name = short(ev.name)
                    text.setdefault(name, ev.name)
                    into.append((name, int(ev.start_ns),
                                 int(ev.duration_ns)))
    devs = sorted(set(ops) | set(modules))
    return Trace(ops=[ops.get(d, []) for d in devs],
                 modules=[modules.get(d, []) for d in devs],
                 host_spans=[], text=text, profile_start_ns=began)


def reduce(trace: Trace, top: int = 8, longest: int = 5) -> dict:
    """busy_s and window_s averaged over the devices, with the breakdown
    the result line carries."""
    lo, hi = window = trace.window()
    busy = [busy_ns(dev, window) for dev in trace.ops]
    all_ops = [e for dev in trace.ops for e in self_times(dev)
               if e[1] < hi and e[1] + e[2] > lo]
    n = max(len(trace.ops), 1)
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "breakdown": {
            "device_ops": [[name, ns / n / 1e9]
                           for name, ns in summed(all_ops)[:top]],
            "idle_gaps": [[attribute(g, trace.host_spans),
                           (g[1] - g[0]) / 1e9]
                          for g in gaps(trace.ops[0], window)[:longest]],
        },
    }
