"""Device time per step of the operations a scope of the program names.

An operation is told by its label: the `op_name` the compiler gave it, which
holds every `jax.named_scope` around the code it came from and JAX's own
marks of the pass (`jvp(`, `transpose(`, `rematted_computation`), then the
profiler's category of the operation in square brackets, as in

    jit(body)/grads/while/body/closed_call/jvp(ffn)/dot_general [convolution fusion]

A fusion carries the one `op_name` the compiler gave it: its root's, or its
convolution's where it holds one (on the chip an optimizer update fused
behind a weight gradient's convolution reads as that convolution). The
metric is the self time (`perf.trace.self_times`: a `while` does not count
its body twice) on the first device, inside the trace's window, of the
operations in whose label `include` is found and `exclude` is not, over the
number of executions of the step. Nothing to read gives nothing, never 0.

Where the labels are. On this runtime (jax 0.9.0, TPU v5e) an event of the
"XLA Ops" line has the operation's HLO text without `metadata={...}` for a
name and its own stats hold only times. The `op_name` (stat `tf_op`) and
the category (stat `hlo_category`) are stats of the event's *metadata*
(`XEventMetadata.stats`), which `jax.profiler.ProfileData` does not show.
So the reader opens the newest `.xplane.pb` under `<root>/.perf_trace/`
itself and reads just those two stats out of the protocol buffer's wire
format: forty lines, no dependency beyond the standard library.
"""
from __future__ import annotations

import functools
import glob
import os
import re

from perf import trace as tracing
from perf.manifest import ROOT

LABEL_STATS = ("tf_op", "hlo_category")


def _varint(buf, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one message: an int for a
    varint, the bytes for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {kind} in an .xplane.pb")
        yield key >> 3, value


def _map_entry(buf):
    """The value message of a `map<int64, message>` entry."""
    return next(v for f, v in _fields(buf) if f == 2)


def labels_of_xspace(data: bytes) -> dict:
    """{operation's short name: label} over the device planes of a
    serialized `XSpace` (the content of an `.xplane.pb`)."""
    labels: dict = {}
    for f, plane in _fields(memoryview(data)):
        if f != 1:                                   # XSpace.planes
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:                               # XPlane.name
                name = bytes(v).decode()
            elif f == 4:                             # .event_metadata
                events.append(_map_entry(v))
            elif f == 5:                             # .stat_metadata
                meta = dict(_fields(_map_entry(v)))  # id 1, name 2
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not tracing.DEVICE_PLANE.match(name):
            continue
        for event in events:
            text, found = "", {}
            for f, v in _fields(event):
                if f == 2:                           # XEventMetadata.name
                    text = bytes(v).decode()
                elif f == 5:                         # .stats
                    stat = dict(_fields(v))
                    key = stat_names.get(stat.get(1))   # XStat.metadata_id
                    if key not in LABEL_STATS:
                        continue
                    if 5 in stat:                    # .str_value
                        found[key] = bytes(stat[5]).decode()
                    elif 7 in stat:                  # .ref_value
                        found[key] = stat_names.get(stat[7], "")
            if "tf_op" in found:
                labels.setdefault(tracing.short(text), "%s [%s]" % (
                    found["tf_op"].rstrip(":"),
                    found.get("hlo_category", "")))
    return labels


def newest_xplane():
    found = glob.glob(os.path.join(ROOT, ".perf_trace", "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


@functools.lru_cache(maxsize=1)
def _labels_at(path: str, mtime_ns: int) -> dict:
    """A trace file's labels, decoded once for all the metrics of a run."""
    with open(path, "rb") as f:
        return labels_of_xspace(f.read())


def scope_ns(trace, labels: dict, include: str, exclude: str = "") -> int:
    """Summed self time, on the first device and inside the trace's window,
    of the operations whose label `include` finds and `exclude` does not."""
    lo, hi = trace.window()
    want = re.compile(include)
    skip = re.compile(exclude) if exclude else None
    total = 0
    for name, start, self_ns in tracing.self_times(trace.ops[0]):
        label = labels.get(name)
        if (label is None or start < lo or start >= hi
                or not want.search(label) or (skip and skip.search(label))):
            continue
        total += self_ns
    return total


def read(ctx, include: str, step_pattern: str, exclude: str = ""):
    t = ctx["trace"]
    if t is None or not any(t.ops) or not any(t.modules):
        return None
    step_rx = re.compile(step_pattern)
    steps = sum(1 for name, _, _ in t.modules[0] if step_rx.search(name))
    path = newest_xplane()
    if not steps or path is None:
        return None
    labels = _labels_at(path, os.stat(path).st_mtime_ns)
    ns = scope_ns(t, labels, include, exclude)
    return ns / steps / 1e6 if ns else None
