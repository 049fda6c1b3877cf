"""Benchmark: ResNet-50 training throughput (images/sec/chip) on one chip.

Matches the reference's headline benchmark — synchronous-SGD ResNet-50
throughput (reference README.md:203-209; harness
srcs/python/kungfu/tensorflow/v1/benchmarks/__main__.py) — through this
framework's distributed train step (1-lane mesh; the collective path
compiles in, so the single-chip number is an end-to-end step time).

Baseline: 8xV100 NCCL ResNet-50 sync training ≈ 360 images/sec per GPU
(fp32, per-GPU batch 64 — the Horovod-era configuration the reference
benchmarks against; BASELINE.json north star: match or beat per-chip).

One process, one measurement.  It requires a TPU: without one it exits
non-zero and prints no metric.  Prints ONE JSON line: {"metric",
"value", "unit", "vs_baseline", "platform", "device_kind",
"device_count", "sync"}.  (ROADMAP S0 replaces this with a table of
cells.)
"""
import argparse
import json
import sys
import time

BASELINE_IMG_PER_SEC_PER_CHIP = 360.0  # 8xV100 NCCL ResNet-50, per GPU


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=5)
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(f"bench: needs a TPU, jax found "
              f"{jax.default_backend()!r}; no metric", file=sys.stderr)
        return 1

    import jax.numpy as jnp
    import numpy as np
    import optax

    import kungfu_tpu.optimizers as kfopt
    from kungfu_tpu.comm.mesh import flat_mesh
    from kungfu_tpu.models import ResNet50
    from kungfu_tpu.training import (build_train_step_with_state,
                                     init_opt_state, replicate)
    from kungfu_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    print(f"bench: platform={devices[0].platform} "
          f"device_kind={devices[0].device_kind!r} count={len(devices)} "
          f"compile_cache={cache_dir}", file=sys.stderr)

    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    mesh = flat_mesh(n=1)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(args.batch, 224, 224, 3).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 10, size=args.batch))
    variables = model.init(jax.random.PRNGKey(0), x[:8])
    params, bstats = variables["params"], variables["batch_stats"]

    def loss_fn(p, mstate, b):
        bx, by = b
        logits, updated = model.apply({"params": p, "batch_stats": mstate},
                                      bx, train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, by).mean()
        return loss, updated["batch_stats"]

    opt = kfopt.synchronous_sgd(
        optax.sgd(0.1, momentum=0.9, nesterov=True))
    sp = replicate(params, mesh)
    sms = replicate(bstats, mesh)
    st = init_opt_state(opt, sp, mesh)
    # NOTE: no compute_dtype here — an earlier measurement had it 20%
    # SLOWER for ResNet-50 (25M params: the upfront cast pass breaks
    # XLA's fuse-cast-into-conv pattern and saves nothing).  Mixed-
    # precision master weights pay off for GPT-class models whose weight
    # bytes rival the activations (benchmarks/gpt.py uses it).
    step = build_train_step_with_state(loss_fn, opt, mesh, donate=False)

    for _ in range(args.warmup):
        sp, st, sms, loss = step(sp, st, sms, (x, y))
    jax.block_until_ready(loss)

    t0 = time.perf_counter()
    for _ in range(args.iters):
        sp, st, sms, loss = step(sp, st, sms, (x, y))
    jax.block_until_ready((sp, loss))
    dt = time.perf_counter() - t0

    if not np.isfinite(float(np.asarray(loss)[0])):
        print("bench: non-finite loss; no metric", file=sys.stderr)
        return 1
    img_per_sec = args.batch * args.iters / dt
    print(json.dumps({
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(img_per_sec, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(img_per_sec / BASELINE_IMG_PER_SEC_PER_CHIP, 3),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "sync": "block_until_ready",
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
