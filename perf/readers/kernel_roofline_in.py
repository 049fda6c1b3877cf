"""`kernel_roofline` for a family whose counts live in a module of their
own: the least time the chip could take for the work the algorithm needs in
one step (function `min_seconds` of `perf/<module>.py`, from shapes alone)
over the device time the kernel's events took per step in the traced
stretch. Nothing to read gives nothing, never 0."""
import importlib
import re

from perf import trace as tracing


def read(ctx, module: str, pattern: str, min_seconds: str,
         step_pattern: str):
    t = ctx["trace"]
    if t is None or not any(t.ops):
        return None
    step_rx = re.compile(step_pattern)
    steps = sum(1 for name, _, _ in t.modules[0] if step_rx.search(name))
    kernel_ns = tracing.kernel_ns(t, pattern)
    if not kernel_ns or not steps:
        return None
    counts = importlib.import_module("perf." + module)
    least = getattr(counts, min_seconds)(ctx["config"], ctx["traffic"],
                                         ctx["peaks"])
    return 100.0 * least / (kernel_ns / steps / 1e9)
