"""The operations and bytes the `smallthinker` family needs, from shapes
alone: the counts of `perf/work.py` for layers of two kinds and a routed
feed-forward of which this chip holds a share. A token meets every
attention weight, the router, the output head, and of the experts the fair
share: `experts per token x held / routed over` experts a layer (6 x 16/64 =
1.5), whatever the router did in a run. A windowed layer's attention is
counted by the pairs a query may see. Recomputation earns no credit.
"""
from __future__ import annotations


def _sizes(config: dict):
    return (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["moe_ffn_hidden_size"])


def attention_params(config: dict) -> int:
    """Weights of one layer's q, k, v and output projections."""
    d, h, hkv, dh, _ = _sizes(config)
    return d * h * dh + 2 * d * hkv * dh + h * dh * d


def expert_params(config: dict) -> int:
    """Weights of ONE expert: gate, up and down."""
    d, _, _, _, f = _sizes(config)
    return 3 * d * f


def fair_experts_per_token(config: dict) -> float:
    """Held experts a token meets in a layer when every expert is as
    popular as every other."""
    return (config["moe_num_active_primary_experts"]
            * config["moe_num_primary_experts"]
            / config["published"]["moe_num_primary_experts"])


def matmul_params_per_token(config: dict) -> float:
    """Weights a token is multiplied by: the layers' attention, router and
    fair share of experts, and the output head (the embedding is a
    lookup)."""
    d = config["hidden_size"]
    layer = (attention_params(config)
             + d * config["published"]["moe_num_primary_experts"]
             + fair_experts_per_token(config) * expert_params(config))
    return (config["num_hidden_layers"] * layer + d * config["vocab_size"])


def visible_pairs(seq_len: int, window) -> int:
    """(query, key) pairs of one causal sequence; with a window, query i
    sees the `window` newest positions, itself included."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def layer_windows(config: dict) -> list:
    """Each kept layer's window, None for a full layer."""
    n = config["num_hidden_layers"]
    return [config["sliding_window_size"] if w else None
            for w in config["sliding_window_layout"][:n]]


def attention_train_flops(config: dict, seq_len: int, window) -> int:
    """One sequence in one layer, forward and backward: QK^T and PV
    forward, four products backward, two operations a multiply-add, over
    the visible pairs."""
    _, h, _, dh, _ = _sizes(config)
    return 12 * visible_pairs(seq_len, window) * h * dh


def attention_train_bytes(config: dict, seq_len: int,
                          itemsize: int = 2) -> int:
    """Least traffic of the same (`perf/work.py`'s count: forward reads q,
    k, v and writes o; backward reads q, k, v, o, do and writes dq, dk,
    dv; K and V have the KV heads only)."""
    _, h, hkv, dh, _ = _sizes(config)
    q, kv = seq_len * h * dh, seq_len * hkv * dh
    return itemsize * ((2 * q + 2 * kv) + (4 * q + 4 * kv))


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """Forward and backward: 6 per weight a token meets in a product, plus
    each layer's attention over its visible pairs."""
    t = traffic["seq_len"]
    attn = sum(attention_train_flops(config, t, w)
               for w in layer_windows(config)) / t
    return 6 * matmul_params_per_token(config) + attn


def attention_train_min_seconds(config: dict, traffic: dict,
                                peaks: dict) -> float:
    """Least time one step's attention can take on the chip: each layer's
    visible pairs, the larger of operations over peak and bytes over
    bandwidth."""
    t = traffic["seq_len"]
    return traffic["batch"] * sum(
        max(attention_train_flops(config, t, w) / peaks["flops_bf16"],
            attention_train_bytes(config, t) / peaks["hbm_bytes_per_s"])
        for w in layer_windows(config))


def fair_rows_per_step(config: dict, traffic: dict) -> float:
    """Rows of one layer's grouped products in a step, at the fair share."""
    return (traffic["batch"] * traffic["seq_len"]
            * fair_experts_per_token(config))


def grouped_train_flops(config: dict, traffic: dict) -> float:
    """One layer's grouped products in a step: three products forward
    (gate, up, down) and six backward (each one's rows and weights), two
    operations a multiply-add, over the fair share's rows."""
    d, _, _, _, f = _sizes(config)
    return 9 * 2 * fair_rows_per_step(config, traffic) * d * f


def grouped_train_bytes(config: dict, traffic: dict,
                        itemsize: int = 2) -> float:
    """Least traffic of the same: each product reads its rows and its
    weights and writes its result once; the held experts' weights are read
    (and their gradients written) once a microbatch, since a microbatch's
    rows are all that is there to multiply."""
    d, _, _, _, f = _sizes(config)
    rows = fair_rows_per_step(config, traffic)
    weights = (config["moe_num_primary_experts"] * expert_params(config)
               * traffic["accum_steps"])
    # forward: rows in (d), gate and up out (2f), the gated rows in (f),
    # down out (d); backward: every one of them again as a cotangent, and
    # the forward's inputs read again for the weights' gradients
    acts = rows * ((d + 2 * f + f + d) + (d + 2 * f + f + d) + (d + f))
    return itemsize * (acts + 3 * weights)


def grouped_train_min_seconds(config: dict, traffic: dict,
                              peaks: dict) -> float:
    """Least time one step's grouped products can take on the chip, every
    layer's: the larger of operations over peak and bytes over bandwidth."""
    return config["num_hidden_layers"] * max(
        grouped_train_flops(config, traffic) / peaks["flops_bf16"],
        grouped_train_bytes(config, traffic) / peaks["hbm_bytes_per_s"])
