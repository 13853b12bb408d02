"""Operations and bytes the algorithm needs, from shapes and the true
lengths of each lane (never from padded tensors), so that any
implementation of the same work is read against the same count.

A step record is what the engine handed to the device for one
dispatch: the prefill chunks as (start, length) and the decode lanes'
valid KV lengths (context including the fed token).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence, Tuple

import numpy as np

BF16 = 2


@dataclass
class Step:
    chunks: List[Tuple[int, int]]     # (start position, tokens)
    dec_lens: List[int]               # valid KV length per decode lane


def decode_attention(dims, layer: int,
                     lens: Sequence[int]) -> Tuple[float, float]:
    """FLOPs and HBM bytes of one layer's decode attention over lanes
    of KV length ``lens`` (the query at position length - 1): q.K and
    p.V (2 * 2 * heads * head_dim per key the query reads there),
    reading each such key and value once (bf16) and the query and
    output once."""
    H, KH, D = dims.heads, dims.kv_heads, dims.head_dim
    n = int(np.sum(dims.keys_read(layer, np.asarray(lens, np.int64) - 1)))
    flops = 4.0 * H * D * n
    byts = 2.0 * KH * D * BF16 * n + 2.0 * H * D * BF16 * len(lens)
    return flops, byts


def least_time(flops: float, byts: float, peaks: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peaks["bf16_flops_per_s"],
               byts / peaks["hbm_bytes_per_s"])


def step_flops(dims, step: Step) -> float:
    """Model FLOPs of one step's tokens through the layers, summed over
    layers: each layer's matmul weights for every token, and attention
    over the keys each token's query reads in that layer. The head is
    counted apart (``head_flops``), on emitted tokens only."""
    H, D = dims.heads, dims.head_dim
    pos = np.concatenate([np.arange(s, s + n, dtype=np.int64)
                          for s, n in step.chunks]
                         + [np.asarray(step.dec_lens, np.int64) - 1])
    total = 0
    for layer in range(dims.layers):
        keys = int(np.sum(dims.keys_read(layer, pos)))
        total += (2 * dims.layer_matmul_params(layer) * len(pos)
                  + 4 * H * D * keys)
    return float(total)


def head_flops(dims, emitted: int) -> float:
    return 2.0 * dims.d * dims.vocab * emitted
