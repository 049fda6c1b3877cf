"""The operations and bytes the algorithms need, from shapes alone.

These are the yardstick of every share of a peak: what the mathematics
requires, not what a kernel happens to do. Recomputation (remat, the chunked
loss re-making its logits, flash attention re-making its scores) earns no
credit, so a share stays comparable when a kernel is replaced.
"""
from __future__ import annotations


def gpt_layer_params(config: dict) -> int:
    """Weights in the matrix products of one decoder layer."""
    d, h, hkv = (config["hidden_size"], config["num_attention_heads"],
                 config["num_key_value_heads"])
    dh = config.get("head_dim", d // h)
    attn = d * h * dh + 2 * d * hkv * dh + h * dh * d
    return attn + 3 * d * config["intermediate_size"]


def gpt_matmul_params(config: dict) -> int:
    """Weights every token is multiplied by: the layers and the output head
    (the embedding is a lookup)."""
    return (config["num_hidden_layers"] * gpt_layer_params(config)
            + config["hidden_size"] * config["vocab_size"])


def attention_train_flops(config: dict, seq_len: int) -> int:
    """Causal attention, forward and backward, of ONE sequence in ONE layer:
    QK^T and PV forward, four products backward, each over the visible half
    of the [T, T] square."""
    d = config["num_attention_heads"] * config.get(
        "head_dim", config["hidden_size"] // config["num_attention_heads"])
    return 6 * seq_len * seq_len * d


def attention_train_bytes(config: dict, seq_len: int, itemsize: int = 2) -> int:
    """Least traffic of the same: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv. K and V have the
    KV heads only."""
    h, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    dh = config.get("head_dim", config["hidden_size"] // h)
    q, kv = seq_len * h * dh, seq_len * hkv * dh
    return itemsize * ((2 * q + 2 * kv) + (4 * q + 4 * kv))


def gpt_train_flops_per_token(config: dict, traffic: dict) -> float:
    """Forward and backward: 6 per weight in a product, plus attention."""
    t = traffic["seq_len"]
    attn = config["num_hidden_layers"] * attention_train_flops(config, t) / t
    return 6 * gpt_matmul_params(config) + attn


def resnet_forward_macs(config: dict) -> int:
    """Multiply-accumulates of one image's forward pass, from the layer
    shapes: every convolution and the classifier."""
    size = config["image_size"]
    nf = config["num_filters"]

    def conv(hw, k, cin, cout):
        return hw * hw * k * k * cin * cout

    hw = size // 2
    macs = conv(hw, 7, 3, nf)
    hw //= 2                                    # max pool
    cin = nf
    for stage, count in enumerate(config["stage_sizes"]):
        f = nf * 2 ** stage
        for j in range(count):
            stride = 2 if stage > 0 and j == 0 else 1
            macs += conv(hw, 1, cin, f)         # before the stride
            out = hw // stride
            macs += conv(out, 3, f, f) + conv(out, 1, f, 4 * f)
            if j == 0:
                macs += conv(out, 1, cin, 4 * f)
            hw, cin = out, 4 * f
    return macs + cin * config["num_classes"]


def resnet_train_flops_per_image(config: dict, traffic: dict) -> float:
    """Forward plus backward (twice the forward), two operations a MAC."""
    return 3 * 2 * resnet_forward_macs(config)


def attention_train_min_seconds(config: dict, traffic: dict,
                                peaks: dict) -> float:
    """Least time one step's attention can take on the chip: the larger of
    operations over peak and bytes over bandwidth."""
    n = traffic["batch"] * config["num_hidden_layers"]
    t = traffic["seq_len"]
    return n * max(attention_train_flops(config, t) / peaks["flops_bf16"],
                   attention_train_bytes(config, t) / peaks["hbm_bytes_per_s"])
