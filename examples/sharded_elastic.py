"""Elastic ZeRO-3 training whose process membership changes at runtime.

The state is SHARDED 1/n per device (flat param + adam m/v vectors, via
``parallel.make_fsdp_step`` semantics), so no process holds the full
model — yet the cluster can shrink on preemption (commits carry a ring
replica) and grow on proposal (joiners pull exactly their range over
the host plane).  Run under the elastic launcher:

    XLA_FLAGS=--xla_force_host_platform_device_count=2 JAX_PLATFORMS=cpu \
        python -m kungfu_tpu.launcher -np 2 -w -builtin-config-port 9180 \
        -- python examples/sharded_elastic.py

then resize it live from another shell:

    python - <<'PY'
    from kungfu_tpu.elastic import put_config, fetch_config
    url = "http://127.0.0.1:9180/config"
    v, c = fetch_config(url)
    put_config(url, c.resize(3))   # grow; shrink with c.resize(1)
    PY

Every worker prints the same loss each step regardless of membership —
the trajectory is resize-invariant (tests/test_elastic_sharded.py
pins it against the no-resize oracle).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np
import optax

from kungfu_tpu.elastic import ShardedElasticTrainer

STEPS = int(os.environ.get("STEPS", "300"))
B = 24  # global batch; every membership's device count must divide it


def loss_fn(p, batch):
    import jax.numpy as jnp
    bx, by = batch
    return jnp.mean((bx @ p["w"] + p["b"] - by) ** 2)


def main():
    rng = np.random.RandomState(0)
    X = rng.randn(B, 32).astype(np.float32)
    Y = X @ rng.randn(32, 8).astype(np.float32)
    tr = ShardedElasticTrainer(
        loss_fn, optax.adam(0.05),
        {"w": np.zeros((32, 8), np.float32),
         "b": np.zeros((8,), np.float32)},
        snapshot_every="auto")
    last = (tr.size, tr.num_devices())
    print(f"[rank {tr.rank}] start: {last[0]} procs x "
          f"{last[1] // last[0]} devices, sharded state "
          f"{tr.local_state_bytes()} B/process", flush=True)
    while tr.step_count < STEPS:
        loss = tr.step((X, Y))
        if loss is None:
            print(f"[rank {tr.rank}] detached by a shrink; exiting",
                  flush=True)
            return
        now = (tr.size, tr.num_devices())
        if now != last:
            print(f"[rank {tr.rank}] resized {last[0]}x{last[1]} -> "
                  f"{now[0]}x{now[1]} (step {tr.step_count})", flush=True)
            last = now
        if tr.step_count % 50 == 0:
            print(f"[rank {tr.rank}] step {tr.step_count}: "
                  f"loss {loss:.6f}", flush=True)
    p = tr.current_params()
    print(f"[rank {tr.rank}] done: |w| = "
          f"{float(np.square(p['w']).sum()):.6f}", flush=True)
    tr.shutdown()


if __name__ == "__main__":
    main()
