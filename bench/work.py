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

BF16 = 2


@dataclass
class Step:
    chunks: List[Tuple[int, int]]     # (start position, tokens)
    dec_lens: List[int]               # valid KV length per decode lane


def decode_attention(dims, lens: Sequence[int]) -> Tuple[float, float]:
    """FLOPs and HBM bytes of one layer's decode attention over lanes
    of KV length ``lens``: q.K and p.V (2 * 2 * heads * head_dim per
    key), reading each valid key and value once (bf16) and the query
    and output once."""
    H, KH, D = dims.heads, dims.kv_heads, dims.head_dim
    n = sum(lens)
    flops = 4.0 * H * D * n
    byts = 2.0 * KH * D * BF16 * n + 2.0 * H * D * BF16 * len(lens)
    return flops, byts


def least_time(flops: float, byts: float, peaks: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peaks["bf16_flops_per_s"],
               byts / peaks["hbm_bytes_per_s"])


def step_flops(dims, step: Step) -> float:
    """Model FLOPs of one step's tokens through the layers: the
    projections and MLP for every token, and attention over each
    token's true causal context. The head is counted apart
    (``head_flops``), on emitted tokens only."""
    L, H, D = dims.layers, dims.heads, dims.head_dim
    tokens = sum(n for _, n in step.chunks) + len(step.dec_lens)
    dense = 2.0 * dims.layer_matmul_params() * tokens
    ctx = sum(n * s + n * (n + 1) / 2 for s, n in step.chunks)
    ctx += sum(step.dec_lens)
    return L * (dense + 4.0 * H * D * ctx)


def head_flops(dims, emitted: int) -> float:
    return 2.0 * dims.d * dims.vocab * emitted
