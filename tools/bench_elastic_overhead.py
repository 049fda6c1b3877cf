"""Elastic step overhead at model scale (round-4 verdict #6).

``DistributedElasticTrainer`` adds three per-step costs on top of the
training step, and round 4 shipped ``snapshot_every=1`` / ``poll_every=1``
defaults without measuring any of them at a real model size.  This
harness measures each component at the 470M-GPT operating point:

1. **fence**: the per-step host-plane allreduce-MAX of one int64
   (measured over 2 launcher-spawned colocated workers, the same
   transport path a pod uses per host);
2. **poll**: one config-server HTTP GET (``fetch_config``);
3. **snapshot**: the device->host commit of params + optimizer state at
   470M scale, measured on the real chip (the replicated trainer copies
   ALL of it; the sharded trainer copies 1/nproc + one ring-replica
   exchange of the same size — reported per-process);
4. **step**: the measured 470M train-step time the costs amortize
   against.

From those it derives the recommended cadences: the largest
``snapshot_every``/``poll_every`` = 1 only if their cost is under the
budget fraction (default 5% of step time), else the smallest cadence
that brings the AMORTIZED cost under budget.  Writes
ELASTIC_OVERHEAD.json.

    python tools/bench_elastic_overhead.py            # full (needs chip)
    python tools/bench_elastic_overhead.py --no-chip  # host costs only
"""
import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

_FENCE_WORKER = r"""
import json, os, time
import numpy as np
from kungfu_tpu import native
from kungfu_tpu.elastic.config_server import fetch_config
from kungfu_tpu.launcher import env as E

p = native.default_peer()
we = E.from_env()
iters = 300
p.barrier(name="bench-start")
t0 = time.perf_counter()
for i in range(iters):
    p.all_reduce(np.asarray([i], np.int64), op="MAX", name=f"fence:{i}")
fence_s = (time.perf_counter() - t0) / iters

polls = 100
t0 = time.perf_counter()
for _ in range(polls):
    fetch_config(we.config_server, timeout=5.0)
poll_s = (time.perf_counter() - t0) / polls

if p.rank == 0:
    with open(os.environ["BENCH_OUT"], "w") as f:
        json.dump({"fence_ms": fence_s * 1e3, "poll_ms": poll_s * 1e3}, f)
"""


def _free_port() -> int:
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def host_plane_costs():
    """Fence + poll, measured over 2 launcher-spawned workers."""
    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "w.py")
        with open(script, "w") as f:
            f.write(_FENCE_WORKER)
        out = os.path.join(td, "out.json")
        env = dict(os.environ, BENCH_OUT=out, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, "-m", "kungfu_tpu.launcher", "-np", "2",
             "-builtin-config-port", str(_free_port()), "--",
             sys.executable, script],
            env=env, capture_output=True, text=True, timeout=120,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
        with open(out) as f:
            return json.load(f)


def chip_costs(preset="470m", steps=3):
    """470M step time + full-state snapshot (D2H) time on the chip."""
    import jax
    import jax.numpy as jnp
    import optax

    from kungfu_tpu.models import gpt as G

    cfg = G.GPTConfig(vocab_size=32768, d_model=1024, n_heads=16,
                      n_kv_heads=8, n_layers=24, d_ff=4096, max_seq=2048,
                      rope=True, mlp="swiglu", dtype=jnp.bfloat16)
    params = jax.jit(lambda k: G.init_params(k, cfg))(jax.random.PRNGKey(0))
    # f32 master weights + adam, the trainer's state shape
    params = jax.tree_util.tree_map(
        lambda t: t.astype(jnp.float32) if t.dtype == jnp.bfloat16 else t,
        params)
    opt = optax.adam(1e-4)
    state = jax.jit(opt.init)(params)

    def loss_fn(p, toks, tgts):
        pb = jax.tree_util.tree_map(
            lambda t: t.astype(jnp.bfloat16)
            if t.dtype == jnp.float32 else t, p)
        logits = G.forward_local(pb, toks, cfg)
        return G.parallel_cross_entropy(logits, tgts).mean()

    @jax.jit
    def step(p, s, toks, tgts):
        loss, g = jax.value_and_grad(loss_fn)(p, toks, tgts)
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s, loss

    rng = np.random.RandomState(0)
    toks = np.asarray(rng.randint(0, 32768, (8, 2048)), np.int32)
    tgts = np.asarray(rng.randint(0, 32768, (8, 2048)), np.int32)
    params, state, loss = step(params, state, toks, tgts)
    float(np.asarray(loss))  # compile + sync
    best = float("inf")
    for _ in range(steps):
        t0 = time.perf_counter()
        params, state, loss = step(params, state, toks, tgts)
        float(np.asarray(loss))
        best = min(best, time.perf_counter() - t0)

    nbytes = sum(t.nbytes for t in jax.tree_util.tree_leaves(params))
    nbytes += sum(t.nbytes for t in jax.tree_util.tree_leaves(state))
    # time the snapshot on a FRESH post-step state each iteration: jax
    # caches an array's host copy, so re-fetching the same arrays
    # measures the cache, not the transfer
    tsnap = float("inf")
    for _ in range(2):
        params, state, loss = step(params, state, toks, tgts)
        float(np.asarray(loss))
        t0 = time.perf_counter()
        jax.tree_util.tree_map(np.asarray, (params, state))
        tsnap = min(tsnap, time.perf_counter() - t0)
    n_params = sum(t.size for t in jax.tree_util.tree_leaves(params))
    return {"step_s": round(best, 3), "snapshot_s": round(tsnap, 3),
            "state_bytes": nbytes, "params_m": round(n_params / 1e6),
            "d2h_gib_s": round(nbytes / tsnap / (1 << 30), 2),
            "tokens_per_step": int(toks.size)}


def recommend(cost_s, step_s, budget=0.05):
    """Smallest cadence whose amortized cost is under budget*step."""
    if cost_s <= budget * step_s:
        return 1
    return int(np.ceil(cost_s / (budget * step_s)))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--no-chip", action="store_true")
    ap.add_argument("--budget", type=float, default=0.05,
                    help="max overhead fraction of step time")
    ap.add_argument("--out", default="ELASTIC_OVERHEAD.json")
    args = ap.parse_args(argv)

    doc = {"host_plane": host_plane_costs()}
    if not args.no_chip:
        import jax
        doc["chip"] = chip_costs()
        doc["chip"]["device"] = str(jax.devices()[0])
        step_s = doc["chip"]["step_s"]
        fence_s = doc["host_plane"]["fence_ms"] / 1e3
        poll_s = doc["host_plane"]["poll_ms"] / 1e3
        snap_s = doc["chip"]["snapshot_s"]
        doc["per_step_overhead_at_defaults_pct"] = round(
            100 * (fence_s + poll_s + snap_s) / step_s, 1)
        doc["recommended"] = {
            "budget_pct": round(100 * args.budget, 1),
            # the fence is NOT skippable (it is the consensus safety
            # mechanism); it has no cadence knob, only a cost row
            "fence_overhead_pct": round(100 * fence_s / step_s, 2),
            "poll_every": recommend(poll_s, step_s, args.budget),
            "snapshot_every": recommend(snap_s, step_s, args.budget),
            "note": ("snapshot_every trades recovery redo distance for "
                     "throughput: recovery replays at most "
                     "snapshot_every steps from the last commit"),
        }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(json.dumps(doc, indent=2))
    return doc


if __name__ == "__main__":
    main()
