"""The one generator of training input: a pool of host batches drawn from
the seed, every row different, cycled for as long as the job runs. What it
draws is set by the traffic file alone (`input`, `batch`, `seq_len`,
`pool_batches`) and the configuration's sizes."""
from __future__ import annotations

import itertools

import numpy as np


def make_pool(config: dict, traffic: dict, seed: int) -> list:
    rng = np.random.Generator(np.random.PCG64(seed))
    n, b = traffic["pool_batches"], traffic["batch"]
    if traffic["input"] == "tokens":
        # one token more than the sequence: targets are the next tokens
        draws = rng.integers(0, config["vocab_size"],
                             (n, b, traffic["seq_len"] + 1), dtype=np.int32)
        return [(d[:, :-1].copy(), d[:, 1:].copy()) for d in draws]
    if traffic["input"] == "images":
        size = config["image_size"]
        return [(rng.integers(0, 256, (b, size, size, 3), dtype=np.uint8),
                 rng.integers(0, config["num_classes"], (b,),
                              dtype=np.int32)) for _ in range(n)]
    raise ValueError(f"unknown input {traffic['input']!r}")


def cycle(pool: list):
    return itertools.cycle(pool)
