"""Seconds of set-up that the program counted from the inside, up to the
window's opening, so that the traced run reports the set-up an untraced run
has and the reference's compile, which follows the window, is left out.

`stage` names what is summed:

    import       the program's own import (`kungfu_tpu.import_seconds`)
    trace_lower  jax tracing the programs and lowering them to MLIR (one
                 union: jax traces again inside a lowering)
    cache_load   retrieving executables from the persistent compile cache
    compile      the backend's time for the programs the cache did not
                 supply: 0 on a warm run

The last three come from the records of the process's newest
`kungfu_tpu.utils.compile_cache.CompileCounter`, which the benchmark's main
makes before it builds anything: jax's own monitoring events, each with its
seconds and its arrival on `perf_counter_ns`, the clock `Outcome.window_ns`
is on. A program without the counter's records or the import's span (the
parent commit's) gives nothing, never 0.
"""
import sys


def read(ctx, stage: str):
    if stage == "import":
        return getattr(sys.modules.get("kungfu_tpu"), "import_seconds", None)
    from kungfu_tpu.utils import compile_cache
    current = getattr(compile_cache, "current_counter", None)
    counter = current() if current else None
    if counter is None:
        return None
    opened_ns = ctx["outcome"].window_ns[0]
    if stage == "trace_lower":
        return counter.seconds(counter.TRACE, counter.LOWER,
                               until_ns=opened_ns)
    if stage == "cache_load":
        return counter.seconds(counter.RETRIEVAL, until_ns=opened_ns)
    if stage == "compile":
        return counter.compile_seconds(opened_ns)
    raise ValueError(f"stage {stage!r}: import, trace_lower, cache_load or "
                     "compile")
