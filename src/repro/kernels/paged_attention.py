"""Paged decode attention — Pallas TPU kernel with page-table-driven
BlockSpecs (the production form of serving/kv_cache.py's page pool).

KV lives in a global page pool, one leaf per layer shaped
[n_pages, KH, R, LANES] (``models.attention.pool_leaf_shape``): each
(page, kv-head) block is the page's tokens row-major, LANES = 128 values
a row, so D = 64 packs two tokens a row. The kernel reads the leaves as
stored — one block is one contiguous [R, LANES] operand — so no step
relays the pool out for it. Each request's pages are scattered
(allocated/evicted/CoW'd by the pool). The kernel never materializes a
request's KV contiguously: the page table is a PREFETCHED SCALAR
operand, and each grid cell's BlockSpec index_map dereferences it —
`k_pages[page_table[b, j], h]` streams exactly one block HBM->VMEM per
cell. This is the TPU analogue of vLLM's paged attention: where the GPU
kernel gathers 16-token blocks per warp, the TPU page is 128+ tokens so
every page forms whole MXU tiles.

Each (b, kv_head, page) cell computes an independent partial softmax
over its page for the G = H//KH query heads; the host-side LSE merge
(shared with flash-decoding) combines partials.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import lse_merge

NEG_INF = float("-inf")


def _kernel(pt_ref, lens_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
            scale: float, page_size: int, head_dim: int):
    """One (lane b, kv-head, page j) cell. Row r of the block holds
    tokens per_row * r + i in lanes [i * D, (i + 1) * D); q_ref holds
    the query once per i, placed in those lanes and zero elsewhere, so
    q_i . row r is token (per_row * r + i)'s score. The output keeps
    all LANES: lanes [i * D, (i + 1) * D) hold slot i's share of p . v,
    which the wrapper folds."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    k = k_ref[0, 0]                                    # [R, LANES]
    v = v_ref[0, 0]
    per_row = k.shape[1] // head_dim
    scores = []
    for i in range(per_row):
        s = jax.lax.dot_general(
            q_ref[0, 0, i], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [G, R]
        # positions within this request: page j covers [j*page, (j+1)*page)
        pos = (j * page_size + i
               + per_row * jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
        scores.append(jnp.where(pos < lens_ref[b], s, NEG_INF))
    m = scores[0].max(-1, keepdims=True)
    for s in scores[1:]:
        m = jnp.maximum(m, s.max(-1, keepdims=True))
    m_safe = jnp.where(m == NEG_INF, 0.0, m)
    lane = jax.lax.broadcasted_iota(jnp.int32, (m.shape[0], k.shape[1]), 1)
    l = acc = 0.0
    for i, s in enumerate(scores):
        p = jnp.exp(s - m_safe)
        l = l + p.sum(-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [G, LANES]
        mine = (lane >= i * head_dim) & (lane < (i + 1) * head_dim)
        acc = acc + jnp.where(mine, pv, 0.0)
    o_ref[0, 0, 0] = acc
    m_ref[0, 0, 0] = m
    l_ref[0, 0, 0] = l


def paged_decode_attention(q, k_pages, v_pages, page_table, lens, *,
                           interpret: bool = False) -> jax.Array:
    """q: [B, H, D]; k/v_pages: pool leaves [n_pages, KH, R, LANES] as
    stored (D must divide LANES); page_table: [B, P] int32 page ids
    (rows beyond a request's length may point anywhere — they are
    masked); lens: [B] valid token counts. Returns [B, H, D]."""
    B, H, D = q.shape
    n_pages, KH, R, lanes = k_pages.shape
    if lanes % D:
        raise ValueError(f"head_dim {D} does not divide the pool's "
                         f"{lanes} lanes")
    per_row = lanes // D
    page_size = R * per_row
    P = page_table.shape[1]
    G = H // KH
    qg = q.reshape(B, KH, G, D)
    # the query once per token slot of a row, in that slot's lanes
    qp = jnp.stack([jnp.pad(qg, ((0, 0), (0, 0), (0, 0),
                                 (i * D, lanes - (i + 1) * D)))
                    for i in range(per_row)], axis=2)
    page_table = jnp.asarray(page_table, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32).reshape(B)

    kernel = functools.partial(_kernel, scale=D ** -0.5,
                               page_size=page_size, head_dim=D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # page_table, lens
        grid=(B, KH, P),
        in_specs=[
            pl.BlockSpec((1, 1, per_row, G, lanes),
                         lambda b, h, j, pt, ln: (b, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, R, lanes),
                         lambda b, h, j, pt, ln: (pt[b, j], h, 0, 0)),
            pl.BlockSpec((1, 1, R, lanes),
                         lambda b, h, j, pt, ln: (pt[b, j], h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, G, lanes),
                         lambda b, h, j, pt, ln: (b, h, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, G, 1),
                         lambda b, h, j, pt, ln: (b, h, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, G, 1),
                         lambda b, h, j, pt, ln: (b, h, j, 0, 0)),
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        # the kernel's own name in compiled programs and profiles
        name="paged_decode_attention",
        out_shape=[
            jax.ShapeDtypeStruct((B, KH, P, G, lanes), jnp.float32),
            jax.ShapeDtypeStruct((B, KH, P, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, KH, P, G, 1), jnp.float32),
        ],
        interpret=interpret,
    )(page_table, lens, qp, k_pages, v_pages)
    acc = acc.reshape(B, KH, P, G, per_row, D).sum(4)
    return lse_merge(acc, m, l).reshape(B, H, D).astype(q.dtype)
