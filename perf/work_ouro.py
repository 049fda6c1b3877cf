"""The operations and bytes a looped decoder needs, from shapes alone: the
counts of `perf/work.py` with the rounds counted in. Every round passes
through every layer and is read by the head, so a token meets the layers'
weights and the head's `total_ut_steps` times, and a step's attention is
`rounds x layers` causal attentions a sequence. Recomputation earns no
credit here either.
"""
from __future__ import annotations

from perf import work


def ouro_matmul_params(config: dict) -> int:
    """Weights every token is multiplied by: each round's layers and each
    round's head (the embedding is a lookup, the exit gate a dot product of
    `hidden_size` numbers, left out)."""
    return config["total_ut_steps"] * work.gpt_matmul_params(config)


def ouro_layer_visits(config: dict) -> int:
    return config["total_ut_steps"] * config["num_hidden_layers"]


def ouro_train_flops_per_token(config: dict, traffic: dict) -> float:
    """Forward and backward: 6 per weight in a product, plus the causal
    attention of every layer visit."""
    t = traffic["seq_len"]
    attn = ouro_layer_visits(config) * work.attention_train_flops(config,
                                                                  t) / t
    return 6 * ouro_matmul_params(config) + attn


def ouro_attention_train_min_seconds(config: dict, traffic: dict,
                                     peaks: dict) -> float:
    """Least time one step's attention can take on the chip: every
    sequence's, in every layer visit, the larger of operations over peak
    and bytes over bandwidth."""
    n = traffic["batch"] * ouro_layer_visits(config)
    t = traffic["seq_len"]
    return n * max(
        work.attention_train_flops(config, t) / peaks["flops_bf16"],
        work.attention_train_bytes(config, t) / peaks["hbm_bytes_per_s"])
