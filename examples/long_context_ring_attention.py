"""Long-context attention via sequence parallelism (ring attention).

The sequence axis is sharded across the mesh; each lane holds T/n tokens
and K/V blocks rotate around the ring with `ppermute` while an online
softmax accumulates — memory per chip stays O(T/n), enabling sequences
that cannot fit on one chip.  (Beyond the reference's DP-only envelope;
see SURVEY.md §2.4.)

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/long_context_ring_attention.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from kungfu_tpu.parallel import (make_ring_attention,
                                 make_ulysses_attention)
from kungfu_tpu.parallel.ring_attention import (make_ring_flash_attention,
                                                reference_attention)


def main():
    devices = jax.devices()
    n = len(devices)
    mesh = Mesh(np.array(devices), ("sp",))
    B, T, H, D = 2, 128 * n, n, 32  # H divisible by n for Ulysses' all-to-all
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(B, T, H, D).astype(np.float32)) * 0.1
               for _ in range(3))

    ring = make_ring_attention(mesh, axis="sp", causal=True)
    ulysses = make_ulysses_attention(mesh, axis="sp", causal=True)
    # ring with Pallas flash chunks — the fast path on TPU pods
    ring_flash = make_ring_flash_attention(mesh, axis="sp", causal=True,
                                           block_q=64, block_k=64)
    dense = reference_attention(q, k, v, causal=True)

    for name, fn in (("ring", ring), ("ulysses", ulysses),
                     ("ring_flash", ring_flash)):
        out = fn(q, k, v)
        err = float(jnp.max(jnp.abs(out - dense)))
        print(f"{name:8s} attention: seq={T} over {n} lanes, "
              f"max err vs dense = {err:.2e}")


if __name__ == "__main__":
    main()
