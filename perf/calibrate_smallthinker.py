"""Readings for the `smallthinker` cell's limits beyond `perf/calibrate.py`'s
(the program against the reference, the fp8 control, the half batch): the
faults only this model can have, each with the plain reference computing the
broken model in the program's place, and the probe of part D (the rows the
held experts multiply at a window's first and last step).

    python3 perf/calibrate_smallthinker.py --workload <cell> \\
        [--fault-seeds 1,2,3] [--probe-seeds 4,5,6 --seconds 20]

A fault is a change to the configuration the broken side reads: five experts
a token in place of six, the six weights not normalised, the windowed layers
run full, RoPE on the layer that has none; and the held experts' part of
every layer's result doubled, which is no key a reference reads: the
family's `held_experts` is wrapped while that side is followed. Prints one
JSON line per reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def faults(config: dict) -> dict:
    """The broken configurations, by name."""
    n = len(config["rope_layout"])
    return {
        "top5": dict(config, moe_num_active_primary_experts=config[
            "moe_num_active_primary_experts"] - 1),
        "not_normalised": dict(config, norm_topk_prob=False),
        "windowed_run_full": dict(config, sliding_window_layout=[0] * n),
        "rope_on_nope": dict(config, rope_layout=[1] * n),
        "experts_doubled": dict(config, fault_expert_scale=2.0),
    }


def follow_broken(reference, family, broken: dict, opt, key, pool):
    """`reference.follow` on a broken configuration. The doubled experts
    are no key any reference reads: for that fault the family's
    `held_experts` is wrapped for the length of the call."""
    from perf.reference import smallthinker as ref
    scale = broken.get("fault_expert_scale")
    plain = ref.held_experts
    if scale:
        ref.held_experts = lambda *a: scale * plain(*a)
    try:
        return reference.follow(family, broken, opt, key, pool)
    finally:
        ref.held_experts = plain


def seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    ap.add_argument("--probe-seeds", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--learning-rate", type=float, default=None,
                    help="probe under another rate than the traffic's")
    ap.add_argument("--root", default=ROOT)
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from kungfu_tpu.comm.mesh import flat_mesh
    from kungfu_tpu.data.pipeline import Prefetcher
    from kungfu_tpu.utils.compile_cache import enable_compile_cache
    from perf import compare, loop, program, traffic_gen, work_smallthinker
    from perf.adapters import smallthinker as adapter
    from perf.manifest import Manifest
    from perf.reference import train as reference
    from perf.run import key_of

    enable_compile_cache()
    cell = Manifest(args.root).cell(args.workload)
    config, traffic = cell["config"], cell["traffic"]
    if args.learning_rate is not None:
        traffic["optimizer"] = dict(traffic["optimizer"],
                                    learning_rate=args.learning_rate)
    family, opt, n = config["family"], traffic["optimizer"], traffic[
        "reference_steps"]

    def say(kind, seed, found, t0):
        print(json.dumps({"kind": kind, "seed": seed, **found,
                          "seconds": round(time.perf_counter() - t0, 1)}),
              flush=True)

    for seed in args.fault_seeds:
        pool = traffic_gen.make_pool(config, traffic, seed)[:n]
        key = key_of(seed)
        ref = reference.follow(family, config, opt, key, pool)
        for name, broken in faults(config).items():
            t0 = time.perf_counter()
            got = follow_broken(reference, family, broken, opt, key, pool)
            say("fault_" + name, seed, compare.numbers(got, ref), t0)

    if args.probe_seeds:
        job = program.build(config, traffic,
                            flat_mesh(jax.devices()[:cell["chips"]]))
        probe = adapter.held_rows_probe(config, traffic)
        fair = (traffic["batch"] * traffic["seq_len"]
                * work_smallthinker.fair_experts_per_token(config))
    for seed in args.probe_seeds:
        pool = traffic_gen.make_pool(config, traffic, seed)
        state = job.init_state(key_of(seed))
        with Prefetcher(traffic_gen.cycle(pool),
                        depth=traffic["prefetch_depth"],
                        place=job.place) as feed:
            # the first batch under the first step's weights, the set-up's
            # steps, a window's worth of steps, then the same batch under
            # the last step's weights: what moved is the routing, not the
            # tokens
            first = np.asarray(probe(state[0], pool[0][0]))
            for _ in range(n):
                state, loss = job.step(state, next(feed))
            jax.block_until_ready(state)
            t0, steps = time.perf_counter(), n
            while time.perf_counter() - t0 < args.seconds:
                state, loss = job.step(state, next(feed))
                jax.block_until_ready(loss)
                steps += 1
            last = np.asarray(probe(state[0], pool[0][0]))
        say("held_rows", seed, {
            "learning_rate": opt["learning_rate"], "steps": steps,
            "first": first.tolist(), "last": last.tolist(),
            "last_over_first": (last / first).tolist(),
            "share_of_fair": (first / fair).tolist()}, t0)
        del state
    return 0


if __name__ == "__main__":
    sys.exit(main())
