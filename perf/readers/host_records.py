"""What the program recorded of its own host side: the records of the
process's newest `kungfu_tpu.utils.compile_cache.CompileCounter` (its
`host`), which the benchmark's main makes before it builds anything. Each is
an interval on `perf_counter_ns`, the clock `Outcome.window_ns` and the
loop's spans are on: jax tracing, lowering, requesting or retrieving a
program (by function), a garbage collection, and for each batch the feed's
staging and its hand-out, joined by one sequence number.

`quantity` names what is read:

    input_stage   ms: the feed's mean host time a batch (the source's next
                  and the placement), over the batches handed out in the
                  window
    input_wait    ms: the consumer's mean wait inside `next()` a hand-out,
                  over the window
    window_stall  ms a window: of the intervals between consecutive
                  hand-outs inside the window, those longer than twice
                  their median, each one's excess over the median, summed.
                  A clean window reads 0. Writes one line to stderr on the
                  longest interval and the records that cover most of it
    window_pause  ms a window: the union of the pauses (collections and
                  jax's four stages) inside the window, clipped to it.
                  Writes one line to stderr on what they were, if any
    setup_gc      s: the union of the collections from the counter's making
                  up to the window's opening; it overlaps the set-up's
                  tracing and lowering by design

A program without the records (the parent commit's) gives nothing, never 0.
"""
import statistics
import sys

from perf.trace import attribute, merge


def _counter():
    from kungfu_tpu.utils import compile_cache
    current = getattr(compile_cache, "current_counter", None)
    counter = current() if current else None
    return counter if hasattr(counter, "host") else None


def union_ns(intervals, lo=None, hi=None) -> int:
    """Length of the union of (start, end) intervals, each clipped to
    [lo, hi] where given."""
    lo = float("-inf") if lo is None else lo
    hi = float("inf") if hi is None else hi
    clipped = [(max(s, lo), min(e, hi)) for s, e in intervals]
    return sum(e - s for s, e in merge(c for c in clipped if c[1] > c[0]))


def handed_out(host, kind: str, lo: int, hi: int) -> list:
    """The hand-out records whose batch was handed out in [lo, hi), in
    order of their hand-out."""
    return sorted((r for r in host
                   if r.kind == kind and lo <= r.end_ns < hi),
                  key=lambda r: r.end_ns)


def intervals(moments) -> list:
    """(from, to) between consecutive moments."""
    return list(zip(moments, moments[1:]))


def stall_ns(moments) -> int:
    """Of the intervals between consecutive moments, those longer than twice
    the median, each one's excess over the median, summed."""
    gaps = [b - a for a, b in intervals(moments)]
    if not gaps:
        return 0
    median = statistics.median(gaps)
    return sum(g - median for g in gaps if g > 2 * median)


def covering(interval, events):
    """(label, ns) of the event that covers most of `interval`, with
    `perf/trace.py`'s `attribute` over (label, start_ns, duration_ns)."""
    best = attribute(interval, events)
    cover = max([min(interval[1], s + d) - max(interval[0], s)
                 for n, s, d in events if n == best] + [0])
    return best, cover


def _stall_line(counter, outcome, handouts) -> str:
    moments = [r.end_ns for r in handouts]
    pairs = intervals(moments)
    if not pairs:
        return "no two hand-outs inside the window"
    longest = max(pairs, key=lambda p: p[1] - p[0])
    median = statistics.median(b - a for a, b in pairs)
    events = [(r.label, r.start_ns, r.end_ns - r.start_ns)
              for r in list(counter.host)]
    label, cover = covering(longest, events)
    what = ("none of the program's records covers it" if label == "none"
            else f"{label} covers {cover / 1e6:.3f} ms of it "
                 f"({100 * cover / (longest[1] - longest[0]):.1f}%)")
    span, span_cover = covering(longest, [(n, s, e - s) for n, s, e
                                          in outcome.spans.events])
    loop = ("" if span == "none" else
            f"; of the loop's spans, {span} covers {span_cover / 1e6:.3f} ms")
    return (f"longest interval between hand-outs "
            f"{(longest[1] - longest[0]) / 1e6:.3f} ms (median "
            f"{median / 1e6:.3f} ms), "
            f"{(longest[0] - outcome.window_ns[0]) / 1e9:.3f} s into the "
            f"window: {what}{loop}")


def _pause_line(counter, lo: int, hi: int) -> str:
    by_label: dict = {}
    for r in list(counter.host):
        if r.kind in counter.PAUSES and r.start_ns < hi and r.end_ns > lo:
            n, ns = by_label.get(r.label, (0, 0))
            by_label[r.label] = (n + 1, ns + min(r.end_ns, hi)
                                 - max(r.start_ns, lo))
    loaded = [f"{name} {'from the cache' if hit else 'compiled'}"
              for name, _, _, hit in counter.requests(lo, hi)]
    return ("pauses inside the window: " + ", ".join(
        f"{label} x{n} {ns / 1e6:.3f} ms" for label, (n, ns) in sorted(
            by_label.items(), key=lambda kv: -kv[1][1]))
        + (f"; programs requested: {', '.join(loaded)}" if loaded else ""))


def read(ctx, quantity: str):
    if quantity not in ("input_stage", "input_wait", "window_stall",
                        "window_pause", "setup_gc"):
        raise ValueError(f"quantity {quantity!r}: input_stage, input_wait, "
                         "window_stall, window_pause or setup_gc")
    counter = _counter()
    if counter is None:
        return None
    outcome = ctx["outcome"]
    lo, hi = outcome.window_ns
    host = list(counter.host)
    if quantity == "setup_gc":
        return union_ns([(r.start_ns, r.end_ns) for r in host
                         if r.kind == counter.GC], hi=lo) / 1e9
    if quantity == "window_pause":
        pauses = [(r.start_ns, r.end_ns) for r in host
                  if r.kind in counter.PAUSES]
        if any(s < hi and e > lo for s, e in pauses):
            print("perf: " + _pause_line(counter, lo, hi), file=sys.stderr)
        return union_ns(pauses, lo, hi) / 1e6
    handouts = handed_out(host, counter.HANDOUT, lo, hi)
    if not handouts:
        return None
    if quantity == "input_wait":
        return statistics.fmean(r.end_ns - r.start_ns
                                for r in handouts) / 1e6
    if quantity == "input_stage":
        seqs = {r.seq for r in handouts}
        staged = [r.end_ns - r.start_ns for r in host
                  if r.kind == counter.STAGE and r.seq in seqs]
        return statistics.fmean(staged) / 1e6 if staged else None
    print("perf: " + _stall_line(counter, outcome, handouts), file=sys.stderr)
    return stall_ns([r.end_ns for r in handouts]) / 1e6
