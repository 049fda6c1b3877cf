"""The benchmark's one command:

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It needs a TPU with as many chips as the cell asks for; without one it exits
non-zero and prints no result. Weights and inputs come from `--seed`. The
last line of standard output is the result: `correct`, `attempted`,
`failed`, `metrics`, `device`, with `--trace 1` also `breakdown`, and last
`compared`, each number compared beside its limit. PERF.md says what each
metric means.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()       # set-up counts from here

import argparse    # noqa: E402
import importlib   # noqa: E402
import json        # noqa: E402
import os          # noqa: E402
import sys         # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

NO_CHIP, INCOMPLETE = 3, 4


def log(*a):
    print("perf:", *a, file=sys.stderr, flush=True)


def key_of(seed: int):
    """A PRNG key from any whole number up to a little over 2**31."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                              seed // (2 ** 31))


def device_peak_bytes(stats: dict) -> int:
    """A chip's peak memory from `Device.memory_stats()`. On the TPU runtime
    `peak_bytes_in_use` counts the arrays (state, batches, outputs); what a
    loaded program needs for its temporaries is held apart, as
    `peak_bytes_reserved`, and stays held between its steps. The chip's peak
    is the two together (PERF.md, "The memory gate")."""
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def drive(manifest, cell: dict, seed: int, seconds: float, trace: int,
          devices, peaks: dict, started: float):
    """Everything of a run after the look for a chip: returns the exit code
    and the result (None where there is none to print)."""
    import jax
    from kungfu_tpu.comm.mesh import flat_mesh
    from kungfu_tpu.data.pipeline import Prefetcher
    from kungfu_tpu.utils.compile_cache import (CompileCounter,
                                                enable_compile_cache)
    from perf import compare, loop, program, traffic_gen
    from perf.reference import train as reference

    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    config, traffic = cell["config"], cell["traffic"]
    devices = devices[:cell["chips"]]
    mesh = flat_mesh(devices)
    job = program.build(config, traffic, mesh)
    pool = traffic_gen.make_pool(config, traffic, seed)
    key = key_of(seed)
    ref_steps = traffic["reference_steps"]

    prefetcher = Prefetcher(traffic_gen.cycle(pool),
                            depth=traffic["prefetch_depth"], place=job.place)
    try:
        out = loop.run(
            job, key, prefetcher, seconds, started, ref_steps,
            trace_steps=traffic["trace_steps"] if trace else 0,
            trace_dir=os.path.join(manifest.root, ".perf_trace",
                                   cell["name"]),
            counter=counter)
    finally:
        prefetcher.close()
    del prefetcher
    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = max(device_peak_bytes(s) for s in stats)
    log("device memory: " + ", ".join(
        f"{k} {stats[0].get(k)}" for k in (
            "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")))
    log(f"set-up {out.setup_s:.1f} s, {out.steps} steps in "
        f"{out.window_s:.2f} s, cache {cache_dir}, compiled "
        f"{counter.compiled}, cache hits {counter.cache_hits}")
    log("set-up by stage, seconds since the start: " + ", ".join(
        f"{k} {v:.1f}" for k, v in out.setup_marks.items()))
    if out.compiled_in_window:
        log(f"{out.compiled_in_window} program(s) compiled inside the "
            f"window: not a measurement")
        return INCOMPLETE, None

    # the window is closed and the program's state freed: now the reference
    t_ref = time.perf_counter()
    ref = reference.follow(config["family"], config, traffic["optimizer"],
                           key, pool[:ref_steps])
    ok, compared = compare.decide(compare.numbers(out.first_steps, ref),
                                  cell["limits"])
    log(f"reference took {time.perf_counter() - t_ref:.1f} s")
    correct = bool(ok and out.losses_finite and out.steps > 0)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": int(peak_bytes)}
    result = {"correct": correct, "attempted": out.steps,
              "failed": 0 if out.losses_finite else out.steps}
    metrics = {}
    if trace:
        from perf import trace as tracing
        reduced = tracing.reduce(out.trace)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        ctx = {"rate": out.rate, "outcome": out, "config": config,
               "traffic": traffic, "peaks": peaks, "trace": out.trace,
               "chips": cell["chips"]}
        for m in manifest.metrics("per_layer", cell["name"]):
            read, kw = manifest.reader(m["name"])
            value = read(ctx, **kw)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {"setup_s": out.setup_s, traffic["rate_metric"]: out.rate}
        for m in manifest.metrics("end_to_end", cell["name"]):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    result.update(metrics=metrics, device=device)
    if trace:
        result["breakdown"] = reduced["breakdown"]
    result["compared"] = compared
    for name, (value, limit) in compared.items():
        log(f"compared {name} {value:.6g} limit {limit}")
    log(f"correct {correct}")
    return 0, result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from perf.manifest import Manifest
    manifest = Manifest(ROOT)
    cell = manifest.cell(args.workload)
    try:
        importlib.import_module("kungfu_tpu")
    except ImportError:
        log("the program (kungfu_tpu) is not in this directory; no result")
        return INCOMPLETE

    import jax
    devices = jax.devices()
    if jax.default_backend() != "tpu" or len(devices) < cell["chips"]:
        log(f"needs {cell['chips']} TPU chip(s); jax found "
            f"{len(devices)} x {jax.default_backend()!r}; no result")
        return NO_CHIP
    from perf.peaks import peaks_for
    code, result = drive(manifest, cell, args.seed, args.seconds, args.trace,
                         devices, peaks_for(devices[0].device_kind), STARTED)
    if result is not None:
        sys.stderr.flush()
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
