"""Readings for a training cell's limits (PERF.md, "How correct is decided"):

    python3 perf/calibrate.py --workload <cell> --seeds 1,2,... \\
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3]

For every seed: the program's first steps against the reference (the lower
readings). For every control seed: the reference computed one precision
below the configuration's, in the program's place (the upper readings). For
every fault seed: the reference with half of each batch left out. One
process, so everything compiles once. Prints one JSON line per reading.
`--leaves N` adds, to each of the program's readings, the N leaves whose
gradient norms lie farthest from the reference's, by name: the look that a
large worst-leaf gap asks for.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONTROL = {"bfloat16": "fp8", "float32": "bf16"}


def seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    ap.add_argument("--leaves", type=int, default=0)
    ap.add_argument("--root", default=ROOT)
    args = ap.parse_args(argv)

    import jax
    from kungfu_tpu.comm.mesh import flat_mesh
    from kungfu_tpu.data.pipeline import Prefetcher
    from kungfu_tpu.utils.compile_cache import enable_compile_cache
    from perf import compare, loop, program, traffic_gen
    from perf.manifest import Manifest
    from perf.reference import train as reference
    from perf.run import key_of

    enable_compile_cache()
    cell = Manifest(args.root).cell(args.workload)
    config, traffic = cell["config"], cell["traffic"]
    family, opt, n = config["family"], traffic["optimizer"], traffic[
        "reference_steps"]
    job = program.build(config, traffic,
                        flat_mesh(jax.devices()[:cell["chips"]]))
    control = CONTROL[config["precision"].split()[0]]

    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(jax.eval_shape(
                 lambda k: job.ref_family.init_params(k, config),
                 key_of(0)))[0]]

    def worst_leaves(got, want):
        gaps = compare.leaf_gaps(got["grad_norms"], want["grad_norms"])
        order = sorted(range(len(names)), key=lambda i: -gaps[i])
        return {"worst_grad_leaves": [
            [names[i], round(float(gaps[i]), 5)]
            for i in order[:args.leaves]]}

    def say(kind, seed, found, t0):
        print(json.dumps({"kind": kind, "seed": seed, **found,
                          "seconds": round(time.perf_counter() - t0, 1)}),
              flush=True)

    for seed in sorted(set(args.seeds + args.control_seeds
                           + args.fault_seeds)):
        pool = traffic_gen.make_pool(config, traffic, seed)[:n]
        key = key_of(seed)
        t0 = time.perf_counter()
        ref = reference.follow(family, config, opt, key, pool)
        say("reference_seconds", seed, {}, t0)
        if seed in args.seeds:
            t0 = time.perf_counter()
            with Prefetcher(traffic_gen.cycle(pool),
                            depth=traffic["prefetch_depth"],
                            place=job.place) as feed:
                out = loop.run(job, key, feed, 0.0, t0, n)
            say("program", seed, dict(
                compare.numbers(out.first_steps, ref),
                **(worst_leaves(out.first_steps, ref) if args.leaves
                   else {})), t0)
        if seed in args.control_seeds:
            t0 = time.perf_counter()
            got = reference.follow(family, config, opt, key, pool,
                                   quant=control)
            say("control_" + control, seed, compare.numbers(got, ref), t0)
        if seed in args.fault_seeds:
            t0 = time.perf_counter()
            got = reference.follow(family, config, opt, key, pool,
                                   fault="half_batch")
            say("fault_half_batch", seed, compare.numbers(got, ref), t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
