"""The whole step's share of the chip's peak: the window's own rate times
the operations one unit needs (a function of perf/work.py, named in the
metric's file) over the peak of the chips used."""
from perf import work


def read(ctx, flops_per_unit: str):
    need = getattr(work, flops_per_unit)(ctx["config"], ctx["traffic"])
    return 100.0 * ctx["rate"] * need / (ctx["chips"]
                                         * ctx["peaks"]["flops_bf16"])
