"""Synthetic allreduce microbenchmark.

Port of the reference benchmark CLI
(srcs/python/kungfu/tensorflow/v1/benchmarks/__main__.py): allreduce the
gradient tensors of a fake model (ResNet50 / VGG16 / BERT size tables) for
N steps and report an equivalent data rate, with the same
``RESULT: <mean> +-<err> (GiB/s) {attrs}`` line format so existing
result-scraping (``grep -o RESULT.*``) keeps working.

Methods (the reference's CPU / NCCL / NCCL+CPU axis becomes the TPU axis):
  XLA    — flat-mesh `psum` per tensor (ICI; the NCCL analogue)
  HIER   — 2-level (host × chip) mesh: psum over chips then hosts
           (the NCCL+CPU hierarchical analogue)
  NATIVE — host-side C++ control-plane runtime allreduce over TCP
           (the reference Go CPU transport analogue; needs the launcher:
           ``python -m kungfu_tpu.launcher -np 4 python -m
           kungfu_tpu.benchmarks --method NATIVE``)

``--fuse`` concatenates all tensors into one collective (nccl_fusion knob).

Usage:
    python -m kungfu_tpu.benchmarks --model ResNet50 --method XLA
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python -m kungfu_tpu.benchmarks --method HIER --hosts 2
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

from ..utils import knobs
from . import Gi, measure, show_rate, show_size

_MODEL_KEYS = {
    "ResNet50": "resnet50-imagenet",
    "VGG16": "vgg16-imagenet",
    "BERT": "bert",
    "SLP": "slp-mnist",
}


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="allreduce microbenchmark")
    p.add_argument("--model", default="ResNet50",
                   choices=list(_MODEL_KEYS),
                   help="gradient-size fixture to benchmark")
    p.add_argument("--method", default="XLA", help="XLA | HIER | NATIVE")
    p.add_argument("--fuse", action="store_true", default=False)
    p.add_argument("--max-count", type=int, default=0, help="max grad count")
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--warmup-steps", type=int, default=5)
    p.add_argument("--devices", type=int, default=0,
                   help="mesh size (XLA/HIER); default = all local devices")
    p.add_argument("--hosts", type=int, default=2,
                   help="host-axis length for HIER")
    p.add_argument("--strategy", default="AUTO",
                   help="NATIVE allreduce strategy (STAR/RING/...)")
    return p.parse_args(argv)


def log_detailed_result(value, error, attrs, unit="GiB/s"):
    attr_str = json.dumps(attrs, separators=(",", ":"))
    print("RESULT: %f +-%f (%s) %s" % (value, error, unit, attr_str))


def _sizes_for(args):
    from ..models.fake_model import MODEL_SIZES
    sizes = list(MODEL_SIZES[_MODEL_KEYS[args.model]])
    if args.fuse:
        sizes = [sum(sizes)]
    if args.max_count > 0 and len(sizes) > args.max_count:
        sizes = sizes[:args.max_count]
    return sizes


def _bench_xla(args, sizes):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..comm.mesh import (CHIP_AXIS, HOST_AXIS, PEER_AXIS, flat_mesh,
                             hierarchical_mesh)
    from ..utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ndev = args.devices or len(jax.devices())
    if args.method == "HIER":
        mesh = hierarchical_mesh(args.hosts, jax.devices()[:ndev])
        axes = (CHIP_AXIS, HOST_AXIS)   # ICI first, then DCN
    else:
        mesh = flat_mesh(n=ndev)
        axes = (PEER_AXIS,)
    spec = P(mesh.axis_names)

    def body(xs):
        out = []
        for x in xs:
            for ax in axes:
                x = jax.lax.psum(x, ax)
            out.append(x)
        return out

    fn = jax.jit(jax.shard_map(body, mesh=mesh,
                               in_specs=spec, out_specs=spec))
    # peer-stacked inputs: axis 0 = devices, each device holds one row
    xs = [jnp.ones((ndev, n), jnp.float32) for n in sizes]
    run = lambda: jax.block_until_ready(fn(xs))
    return ndev, run, mesh


def _bench_native(args, sizes):
    from .. import native

    peer = native.default_peer()
    if peer is None:
        sys.exit("NATIVE method needs the launcher (KFT_* env); run via "
                 "python -m kungfu_tpu.launcher -np N ...")
    xs = [np.ones(n, np.float32) for n in sizes]

    def run():
        for i, x in enumerate(xs):
            peer.all_reduce(x, op="SUM", strategy=args.strategy,
                            name=f"bench_{i}")
    return peer.size, run, None


def main(argv=None):
    args = parse_args(argv)
    sizes = _sizes_for(args)
    tot_size = sum(sizes) * 4  # f32 bytes

    if args.method in ("XLA", "HIER"):
        np_, run, _ = _bench_xla(args, sizes)
        rank = 0
    elif args.method == "NATIVE":
        np_, run, _ = _bench_native(args, sizes)
        from .. import native
        rank = native.default_peer().rank
    else:
        sys.exit(f"unknown method {args.method}")

    def log(msg):
        if rank == 0:
            print(msg)

    # reference's "equivalent data rate" convention (__main__.py:135):
    # every peer sends+receives ~2x the payload along a (np-1)-hop path
    multiplier = 4 * (np_ - 1)
    log("all reduce %d tensors of total size: %s among %d peers, using %s" %
        (len(sizes), show_size(tot_size), np_, args.method))

    for step in range(1, args.warmup_steps + 1):
        duration, _ = measure(run)
        log("warmup step %d, took %.2fs, equivalent data rate: %s" %
            (step, duration, show_rate(tot_size * multiplier, duration)))

    values = []
    for step in range(1, args.steps + 1):
        duration, _ = measure(run)
        values.append(tot_size * multiplier / Gi / duration)
        log("step %d, took %.2fs, equivalent data rate: %s" %
            (step, duration, show_rate(tot_size * multiplier, duration)))

    if rank == 0:
        v = np.array(values)
        attrs = {
            "method": args.method,
            "np": np_,
            "model": args.model,
            "fuse": args.fuse,
            "strategy": (args.strategy if args.method == "NATIVE"
                         else knobs.raw("KFT_ALLREDUCE_STRATEGY")),
        }
        log_detailed_result(v.mean(), 1.96 * v.std(), attrs)


if __name__ == "__main__":
    main()
