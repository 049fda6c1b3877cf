"""Mean host time per step inside one span of the benchmark's loop, over the
measured window."""


def read(ctx, span: str):
    out = ctx["outcome"]
    if not out.steps:
        return None
    lo, hi = out.window_ns
    return out.spans.total_ns(span, lo, hi) / out.steps / 1e6
