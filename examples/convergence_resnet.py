"""ResNet / CIFAR-10-shape convergence run — the framework's accuracy
parity artifact.

The reference's headline result is *convergence*, not throughput: every
KungFu optimizer reaches the same top-1 as the Horovod baseline
(reference: README.md:190-199).  This run reproduces that evidence shape
on TPU-native machinery: a bottleneck ResNet on CIFAR-10-shaped data
trained with synchronous SGD to a recorded test-accuracy target, and —
with ``--elastic`` — the same model through mid-train cluster resizes
(reference: scripts/tests/run-elastic-test.sh) reaching the same target.

Static run, through the launcher (2 processes x 4 virtual lanes):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python -m kungfu_tpu.launcher -np 2 -- \\
        python examples/convergence_resnet.py --steps 300

Elastic run (single process, 8 virtual lanes, resizes 8->4->8):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \\
        python examples/convergence_resnet.py --elastic 8:100,4:100,8:100

Real CIFAR-10 is used when ``CIFAR_DIR`` points at the extracted
``cifar-10-batches-py``; otherwise the deterministic class-separable
synthetic set (kungfu_tpu.data.cifar10) stands in — same shapes, same
pipeline, and optimizers genuinely have to fit it.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import kungfu_tpu as kft
import kungfu_tpu.optimizers as kfopt
from kungfu_tpu.comm.mesh import flat_mesh, peer_sharding
from kungfu_tpu.data import cifar10
from kungfu_tpu.models.resnet import ResNet
from kungfu_tpu.training import (broadcast_variables,
                                 build_train_step_with_state,
                                 init_opt_state, replicate)


def make_model():
    dtype = (jnp.bfloat16 if jax.devices()[0].platform == "tpu"
             else jnp.float32)
    return ResNet(stage_sizes=[1, 1, 1], num_filters=16, num_classes=10,
                  dtype=dtype, small_inputs=True)


def make_loss_fn(model):
    def loss_fn(p, mstate, batch):
        x, y = batch
        logits, upd = model.apply({"params": p, "batch_stats": mstate}, x,
                                  train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        return loss, upd["batch_stats"]
    return loss_fn


def evaluate(model, params, batch_stats, x, y, batch=256):
    @jax.jit
    def logits_of(p, m, xb):
        return model.apply({"params": p, "batch_stats": m}, xb, train=False)
    hits = 0
    for i in range(0, len(x) - batch + 1, batch):
        pred = np.asarray(logits_of(params, batch_stats,
                                    jnp.asarray(x[i:i + batch]))).argmax(1)
        hits += int((pred == y[i:i + batch]).sum())
    n = (len(x) // batch) * batch
    return hits / n


def run_static(args, data):
    (xtr, ytr), (xte, yte) = data
    kft.init_distributed()
    mesh = flat_mesh()
    n_lanes = int(np.prod(mesh.devices.shape))
    rank, nproc = jax.process_index(), jax.process_count()
    lanes_per_proc = n_lanes // nproc
    global_batch = args.batch_per_lane * n_lanes
    if rank == 0:
        print(f"static: {nproc} proc x {lanes_per_proc} lanes, "
              f"global batch {global_batch}")

    model = make_model()
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 32, 32, 3)), train=False)
    loss_fn = make_loss_fn(model)
    opt = kfopt.synchronous_sgd(optax.sgd(args.lr, momentum=0.9))
    sp = broadcast_variables(replicate(variables["params"], mesh), mesh)
    sm = replicate(variables["batch_stats"], mesh)
    st = init_opt_state(opt, sp, mesh)
    step = build_train_step_with_state(loss_fn, opt, mesh, donate=False)

    sharding = peer_sharding(mesh)
    local_bs = args.batch_per_lane * lanes_per_proc
    rng = np.random.RandomState(0)  # identical on every process
    loss = None
    for i in range(args.steps):
        idx = rng.randint(0, len(xtr), global_batch)  # global sample
        lo = rank * local_bs                          # this proc's slice
        mine = idx[lo:lo + local_bs]
        gx = jax.make_array_from_process_local_data(
            sharding, xtr[mine])
        gy = jax.make_array_from_process_local_data(
            sharding, ytr[mine])
        sp, st, sm, loss = step(sp, st, sm, (gx, gy))
        if i % 25 == 0:
            # EVERY rank fetches (a local-shard read): it synchronizes
            # the ranks' async dispatch queues.  Fetching on rank 0 only
            # let rank 1 run unboundedly ahead and the cross-process
            # collective stream deadlocked within ~100 steps
            lv = float(np.asarray(loss.addressable_data(0))[0])
            if rank == 0:
                print(f"step {i:4d}: loss {lv:.4f}")

    # every lane is identical under sync SGD: eval this process's replica
    one = lambda tree: jax.tree_util.tree_map(
        lambda t: np.asarray(t.addressable_data(0))[0], tree)
    acc = evaluate(model, one(sp), one(sm), xte, yte)
    if rank == 0:
        final = float(np.asarray(loss.addressable_data(0))[0])
        print(f"test accuracy: {acc:.4f} (target {args.target})")
        report(args, {"mode": "static", "steps": args.steps,
                      "lanes": n_lanes, "processes": nproc,
                      "final_loss": final, "test_accuracy": acc,
                      "target": args.target, "reached": acc >= args.target})
    assert acc >= args.target, f"accuracy {acc:.4f} < target {args.target}"


def run_elastic(args, data):
    from kungfu_tpu.elastic import ElasticDataShard, ElasticTrainer, \
        StepSchedule
    (xtr, ytr), (xte, yte) = data
    schedule = StepSchedule.parse(args.elastic)
    model = make_model()
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 32, 32, 3)), train=False)
    tr = ElasticTrainer(
        make_loss_fn(model),
        optimizer_factory=lambda n: kfopt.synchronous_sgd(
            optax.sgd(args.lr, momentum=0.9)),
        init_params=variables["params"],
        init_model_state=variables["batch_stats"],
        init_size=schedule.size_at(0),
    )
    shard = ElasticDataShard(len(xtr))
    resizes = 0
    loss = float("nan")
    for step_i in range(schedule.total_steps()):
        want = schedule.size_at(step_i)
        if want != tr.n:
            print(f"step {step_i}: resize {tr.n} -> {want}")
            tr.resize(want)
            resizes += 1
        idx = shard.batch_indices(tr.trained_samples,
                                  args.batch_per_lane * tr.n)
        loss = tr.step((jnp.asarray(xtr[idx]), jnp.asarray(ytr[idx])))
        if step_i % 50 == 0:
            print(f"step {step_i:4d} lanes={tr.n} loss={loss:.4f}")

    acc = evaluate(model, tr.current_params(0), tr.current_model_state(0),
                   xte, yte)
    print(f"test accuracy: {acc:.4f} (target {args.target}, "
          f"{resizes} mid-train resizes)")
    report(args, {"mode": "elastic", "schedule": args.elastic,
                  "steps": schedule.total_steps(), "resizes": resizes,
                  "final_loss": loss, "test_accuracy": acc,
                  "target": args.target, "reached": acc >= args.target})
    assert acc >= args.target, f"accuracy {acc:.4f} < target {args.target}"


def report(args, result):
    print("CONVERGENCE " + json.dumps(result))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=2)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch-per-lane", type=int, default=32)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--target", type=float, default=0.95,
                    help="required test accuracy")
    ap.add_argument("--elastic", default=None, metavar="NP:STEPS,...",
                    help="run elastically under this resize schedule")
    ap.add_argument("--json", default=None, help="write result JSON here")
    args = ap.parse_args()

    data = cifar10(os.environ.get("CIFAR_DIR") or None)
    if args.elastic:
        run_elastic(args, data)
    else:
        run_static(args, data)


if __name__ == "__main__":
    main()
