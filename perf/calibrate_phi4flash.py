"""Readings for the `phi4flash` cell's limits beyond `perf/calibrate.py`'s
(the program against the reference, the fp8 control): the faults only this
model can have, each with the plain reference computing the broken model in
the program's place.

    python3 perf/calibrate_phi4flash.py --workload <cell> --fault-seeds 1,2 \\
        [--faults half_positions,...]

A fault swaps one piece of `perf/reference/phi4flash.py` for the length of
a call: the gated memory unit gated by its own input's projection in place
of the memory, the cross-attention reading the windowed layer's K and V in
place of the full layer's, lam at nought, the windowed layer attending to
every earlier position, the scan's step without its softplus, and a step
trained on the first half of each sequence's positions alone (with one
sequence a batch, what `perf/calibrate.py`'s half batch cannot plant; the
layers are causal, so this is the loss over half the positions). The broken
configuration carries the fault's name, which no reference reads, so that
its program is compiled apart. Prints one JSON line per reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _with(sizes, **changed):
    return lambda config: dict(sizes(config), **changed)


def _earlier_kv(sizes):
    """The K and V shared from the attention layer before the full one."""
    def broken(config):
        s = sizes(config)
        kinds = s["kinds"][:s["shared"]]
        return dict(s, shared=len(kinds) - 1 - kinds[::-1].index("swa"))
    return broken


def faults() -> dict:
    """{name: {function of the reference: what stands in its place}}."""
    from perf.reference import phi4flash as ref
    plain_gmu = ref.gmu
    own = lambda mm, layer, h, m: plain_gmu(
        mm, layer, h, mm("td,de->te", h, layer["w_g"]))
    plain_loss = ref.batch_loss

    def first_half(params, batch, config, quant="none"):
        return plain_loss(params, tuple(a[:, :a.shape[1] // 2]
                                        for a in batch), config, quant)
    return {
        "gmu_own_input": {"gmu": own},
        "cross_reads_windowed_kv": {"sizes": _earlier_kv(ref.sizes)},
        "lambda_zero": {"diff_lambda": lambda layer, lam0: 0.0},
        "window_off": {"sizes": _with(ref.sizes, window=None)},
        "no_softplus": {"step_size": lambda t: t},
        "half_positions": {"batch_loss": first_half},
    }


def follow_broken(reference, family, fault: str, config, opt, key, pool):
    """`reference.follow` with one fault planted in
    `perf/reference/phi4flash.py` for the length of the call."""
    from perf.reference import phi4flash as ref
    swaps = faults()[fault]
    plain = {name: getattr(ref, name) for name in swaps}
    for name, broken in swaps.items():
        setattr(ref, name, broken)
    try:
        return reference.follow(family, dict(config, fault=fault), opt, key,
                                pool)
    finally:
        for name, fn in plain.items():
            setattr(ref, name, fn)


def seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    ap.add_argument("--faults", default="",
                    help="the faults to plant, by name (default: all)")
    ap.add_argument("--root", default=ROOT)
    args = ap.parse_args(argv)

    from kungfu_tpu.utils.compile_cache import enable_compile_cache
    from perf import compare, traffic_gen
    from perf.manifest import Manifest
    from perf.reference import train as reference
    from perf.run import key_of

    enable_compile_cache()
    cell = Manifest(args.root).cell(args.workload)
    config, traffic = cell["config"], cell["traffic"]
    family, opt, n = config["family"], traffic["optimizer"], traffic[
        "reference_steps"]
    for seed in args.fault_seeds:
        pool = traffic_gen.make_pool(config, traffic, seed)[:n]
        key = key_of(seed)
        ref = reference.follow(family, config, opt, key, pool)
        for name in args.faults.split(",") if args.faults else faults():
            t0 = time.perf_counter()
            got = follow_broken(reference, family, name, config, opt, key,
                                pool)
            print(json.dumps({"kind": "fault_" + name, "seed": seed,
                              **compare.numbers(got, ref),
                              "seconds": round(time.perf_counter() - t0, 1)}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
