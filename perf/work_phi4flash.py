"""The operations and bytes the `phi4flash` family needs, from shapes alone.
A token meets every weight matrix of its layers (a mamba layer's `W_in`,
`W_x`, `W_dt`, `W_out`; attention's q, k, v and output projections, a
cross layer's q and output; a gated memory unit's two; the SwiGLU
feed-forward) and the head tied to the embedding. Differential attention
is counted by its causal pairs (windowed where the layer is): two maps of
the query heads' half each, scores at the head size and the values at
twice it. The scan is counted by the bytes it has to read and write (a few
operations an element, far under the chip's ratio). Recomputation earns
no credit."""
from __future__ import annotations

from perf.reference import phi4flash as ref
from perf.work_smallthinker import visible_pairs


def mixer_params(config: dict, kind: str) -> int:
    """Weights of one layer's token mixer's products."""
    s = ref.sizes(config)
    D, H, Hkv, Dh, E = s["D"], s["H"], s["Hkv"], s["Dh"], s["E"]
    if kind == "mamba":
        return D * 2 * E + E * (s["R"] + 2 * s["N"]) + s["R"] * E + E * D
    if kind == "gmu":
        return 2 * D * E
    q_and_out = D * H * Dh + (H // 2) * 2 * Dh * D
    if kind == "cross":
        return q_and_out
    return q_and_out + D * Hkv * Dh + D * (Hkv // 2) * 2 * Dh


def matmul_params_per_token(config: dict) -> float:
    """Weights a token is multiplied by in a step's forward."""
    s = ref.sizes(config)
    return (s["D"] * s["V"]
            + sum(mixer_params(config, k) + 3 * s["D"] * s["F"]
                  for k in s["kinds"]))


def _attending(config: dict):
    """(window or None) of each kept layer that attends."""
    s = ref.sizes(config)
    return [s["window"] if k == "swa" else None for k in s["kinds"]
            if k in ("swa", "full", "cross")]


def attention_train_flops(config: dict, seq_len: int, window) -> int:
    """One sequence in one attention layer, forward and backward: per
    visible pair, two maps of H/2 heads, q k^T at Dh and p v at 2 Dh, two
    operations a multiply-add, the backward twice the forward."""
    s = ref.sizes(config)
    per_pair = 2 * (s["H"] // 2) * (s["Dh"] + 2 * s["Dh"]) * 2
    return 3 * per_pair * visible_pairs(seq_len, window)


def attention_train_bytes(config: dict, seq_len: int,
                          itemsize: int = 2) -> int:
    """Least traffic of the same: q, k, v in and o out forward; q, k, v, o,
    do in and dq, dk, dv out backward; o has the query heads at the
    values' width."""
    s = ref.sizes(config)
    q = seq_len * s["H"] * s["Dh"]
    k = seq_len * s["Hkv"] * s["Dh"]
    v = seq_len * (s["Hkv"] // 2) * 2 * s["Dh"]
    o = seq_len * s["H"] * 2 * s["Dh"]
    return itemsize * ((q + k + v + o) + (q + k + v + 2 * o) + (q + k + v))


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """Forward and backward: 6 per weight a token meets in a product, plus
    each attention layer's pairs."""
    t = traffic["seq_len"]
    attn = sum(attention_train_flops(config, t, w)
               for w in _attending(config)) / t
    return 6 * matmul_params_per_token(config) + attn


def attention_train_min_seconds(config: dict, traffic: dict,
                                peaks: dict) -> float:
    """Least time one step's attention can take on the chip: each layer's
    larger of operations over peak and bytes over bandwidth."""
    t = traffic["seq_len"]
    return traffic["batch"] * sum(
        max(attention_train_flops(config, t, w) / peaks["flops_bf16"],
            attention_train_bytes(config, t) / peaks["hbm_bytes_per_s"])
        for w in _attending(config))


def mamba_layers(config: dict) -> int:
    return ref.sizes(config)["kinds"].count("mamba")


def scan_train_bytes(config: dict, traffic: dict) -> float:
    """Least traffic of one step's selective scans, float32: forward reads
    u, delta (E a token) and B, C (N) and writes y (E); backward reads u,
    delta, B, C and the cotangent of y and writes those of u, delta, B and
    C. The state never leaves the chip."""
    s = ref.sizes(config)
    tokens = traffic["batch"] * traffic["seq_len"]
    E, N = s["E"], s["N"]
    forward = 3 * E + 2 * N
    backward = 3 * E + 2 * N + 2 * E + 2 * N
    return 4 * mamba_layers(config) * tokens * (forward + backward)


def scan_train_min_seconds(config: dict, traffic: dict,
                           peaks: dict) -> float:
    """Least time one step's scans can take: bytes over bandwidth."""
    return scan_train_bytes(config, traffic) / peaks["hbm_bytes_per_s"]
