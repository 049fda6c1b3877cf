"""Median device duration of one program's executions in the traced
stretch: the events of the modules line whose name `pattern` finds."""
import re
import statistics


def read(ctx, pattern: str):
    t = ctx["trace"]
    if t is None:
        return None
    rx = re.compile(pattern)
    durs = [d for dev in t.modules for name, _, d in dev if rx.search(name)]
    return statistics.median(durs) / 1e6 if durs else None
