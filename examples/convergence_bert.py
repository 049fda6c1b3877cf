"""BERT-tiny MLM convergence under SMA + gradient-noise-scale monitoring.

The convergence-evidence companion to examples/bert_sma_gns.py: that
example demos the wiring on uniform-random tokens (whose MLM loss cannot
drop below ln(V)); this one trains on *learnable* synthetic text — a
fixed bank of template sentences with random masking — so the loss curve
is a real convergence signal, recorded start -> end with a target.
Reference analogue: the BERT+SMA configuration of the convergence study
(reference: README.md:190-199) with the GNS monitor running online
(MonitorGradientNoiseScaleOptimizer).

Through the launcher (2 processes x 4 virtual lanes):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python -m kungfu_tpu.launcher -np 2 -- \\
        python examples/convergence_bert.py --steps 200
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
from kungfu_tpu.models import bert_tiny
from kungfu_tpu.training import (broadcast_variables, build_train_step,
                                 init_opt_state, replicate)

VOCAB, SEQ, MASK_ID, TEMPLATES = 512, 64, 0, 64


def template_bank():
    """A fixed bank of 'sentences'.  Any unmasked context identifies the
    template, so masked tokens are predictable — tiny-BERT memorizes the
    bank and the MLM loss falls toward zero."""
    rng = np.random.RandomState(7)
    return rng.randint(1, VOCAB, (TEMPLATES, SEQ)).astype(np.int32)


def sample_batch(bank, rng, n):
    tokens = bank[rng.randint(0, len(bank), n)]
    is_masked = rng.rand(*tokens.shape) < 0.15
    masked = np.where(is_masked, MASK_ID, tokens)
    return (tokens.astype(np.int32), masked.astype(np.int32),
            is_masked.astype(np.float32))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch-per-lane", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--target", type=float, default=5.0,
                    help="required final MLM loss (upper bound)")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    kft.init_distributed()
    mesh = flat_mesh()
    n_lanes = int(np.prod(mesh.devices.shape))
    rank, nproc = jax.process_index(), jax.process_count()
    lanes_per_proc = n_lanes // nproc
    global_batch = args.batch_per_lane * n_lanes

    model = bert_tiny(vocab_size=VOCAB, max_len=SEQ,
                      dtype=jnp.bfloat16
                      if jax.devices()[0].platform == "tpu"
                      else jnp.float32)
    init_tokens = jnp.zeros((2, SEQ), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), init_tokens, train=False)

    def loss_fn(p, batch):
        tokens, masked, is_masked = batch
        logits = model.apply(p, masked, train=True)
        nll = optax.softmax_cross_entropy_with_integer_labels(logits, tokens)
        return (nll * is_masked).sum() / jnp.maximum(is_masked.sum(), 1)

    # SMA + GNS exactly as in bert_sma_gns.py: local gradients applied,
    # replicas pulled toward the average, noise scale from the same psums
    opt = kfopt.synchronous_averaging(
        kfopt.gradient_noise_scale(optax.adam(args.lr),
                                   batch_size=args.batch_per_lane,
                                   apply="local"),
        alpha=0.1)
    sp = broadcast_variables(replicate(params, mesh), mesh)
    st = init_opt_state(opt, sp, mesh)
    step = build_train_step(loss_fn, opt, mesh, donate=False)

    bank = template_bank()
    sharding = peer_sharding(mesh)
    local_bs = args.batch_per_lane * lanes_per_proc
    rng = np.random.RandomState(0)  # identical streams; each proc slices
    curve = []
    for i in range(args.steps):
        tokens, masked, is_masked = sample_batch(bank, rng, global_batch)
        lo = rank * local_bs
        batch = tuple(
            jax.make_array_from_process_local_data(sharding,
                                                   a[lo:lo + local_bs])
            for a in (tokens, masked, is_masked))
        sp, st, loss = step(sp, st, batch)
        if i % 20 == 0 or i == args.steps - 1:
            lv = float(np.asarray(loss.addressable_data(0))[0])
            ns = float(np.asarray(st.noise_scale.addressable_data(0))[0])
            curve.append({"step": i, "mlm_loss": round(lv, 4),
                          "noise_scale": round(ns, 1)})
            if rank == 0:
                print(f"step {i:4d}: mlm_loss={lv:.4f} noise_scale={ns:.1f}")

    final = curve[-1]["mlm_loss"]
    if rank == 0:
        result = {"mode": "bert_sma_gns", "steps": args.steps,
                  "lanes": n_lanes, "processes": nproc,
                  "initial_loss": curve[0]["mlm_loss"],
                  "final_loss": final, "curve": curve,
                  "target": args.target, "reached": final <= args.target}
        print("CONVERGENCE " + json.dumps(
            {k: v for k, v in result.items() if k != "curve"}))
        if args.json:
            with open(args.json, "w") as f:
                json.dump(result, f, indent=2)
    assert final <= args.target, f"loss {final:.4f} > target {args.target}"


if __name__ == "__main__":
    main()
