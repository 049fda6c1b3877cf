"""A scope's or a named kernel's share of its roofline: the least time the
chip could take for the work the algorithm needs in one step (function
`min_seconds` of `perf/<module>.py`, from shapes alone) over the device time
per step of the operations whose label `include` finds (`scope_ms`'s
labels: the `op_name` with the program's scopes and a kernel's own name, so
a kernel is told from the other Mosaic calls of the step). Nothing to read
gives nothing, never 0."""
import importlib

from perf.readers import scope_ms


def read(ctx, module: str, include: str, min_seconds: str,
         step_pattern: str, exclude: str = ""):
    ms = scope_ms.read(ctx, include, step_pattern, exclude)
    if not ms:
        return None
    counts = importlib.import_module("perf." + module)
    least = getattr(counts, min_seconds)(ctx["config"], ctx["traffic"],
                                         ctx["peaks"])
    return 100.0 * least / (ms / 1e3)
