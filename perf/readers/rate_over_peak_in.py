"""`rate_over_peak` for a family whose counts live in a module of their own:
the window's rate times the operations one unit needs (function
`flops_per_unit` of `perf/<module>.py`) over the peak of the chips used."""
import importlib


def read(ctx, module: str, flops_per_unit: str):
    counts = importlib.import_module("perf." + module)
    need = getattr(counts, flops_per_unit)(ctx["config"], ctx["traffic"])
    return 100.0 * ctx["rate"] * need / (ctx["chips"]
                                         * ctx["peaks"]["flops_bf16"])
