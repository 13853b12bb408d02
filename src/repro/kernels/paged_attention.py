"""Paged decode attention — Pallas TPU kernel with page-table-driven
BlockSpecs (the production form of serving/kv_cache.py's page pool).

KV lives in a global page pool [n_pages, page_size, KH, D]; each
request's pages are scattered (allocated/evicted/CoW'd by the pool).
The kernel never materializes a request's KV contiguously: the page
table is a PREFETCHED SCALAR operand, and each grid cell's BlockSpec
index_map dereferences it — `k_pages[page_table[b, j]]` streams exactly
one page HBM->VMEM per cell. This is the TPU analogue of vLLM's paged
attention: where the GPU kernel gathers 16-token blocks per warp, the
TPU page is 128+ tokens so every page forms whole MXU tiles.

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
            scale: float, page_size: int):
    b = pl.program_id(0)
    j = pl.program_id(2)
    q = q_ref[0, 0]                                    # [G, D]
    k = k_ref[0, 0]                                    # [page, D]
    v = v_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # [G, page]
    # positions within this request: page j covers [j*page, (j+1)*page)
    pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < lens_ref[b], s, NEG_INF)
    m = s.max(-1, keepdims=True)
    m_safe = jnp.where(m == NEG_INF, 0.0, m)
    p = jnp.exp(s - m_safe)
    l = p.sum(-1, keepdims=True)
    acc = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # [G, D]
    o_ref[0, 0, 0] = acc
    m_ref[0, 0, 0] = m
    l_ref[0, 0, 0] = l


def paged_decode_attention(q, k_pages, v_pages, page_table, lens, *,
                           interpret: bool = False) -> jax.Array:
    """q: [B, H, D]; k/v_pages: [n_pages, page_size, KH, D];
    page_table: [B, P] int32 page ids (rows beyond a request's length
    may point anywhere — they are masked); lens: [B] valid token counts.
    Returns [B, H, D]."""
    B, H, D = q.shape
    n_pages, page_size, KH, _ = k_pages.shape
    P = page_table.shape[1]
    G = H // KH
    qg = q.reshape(B, KH, G, D)
    # kernel-side page layout: [n_pages, KH, page, D] so one (page,
    # kv-head) block is a contiguous [page, D] MXU operand
    kp = k_pages.transpose(0, 2, 1, 3)
    vp = v_pages.transpose(0, 2, 1, 3)
    page_table = jnp.asarray(page_table, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32).reshape(B)

    kernel = functools.partial(_kernel, scale=D ** -0.5,
                               page_size=page_size)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # page_table, lens
        grid=(B, KH, P),
        in_specs=[
            pl.BlockSpec((1, 1, G, D),
                         lambda b, h, j, pt, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, D),
                         lambda b, h, j, pt, ln: (pt[b, j], h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, D),
                         lambda b, h, j, pt, ln: (pt[b, j], h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, G, D),
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
            jax.ShapeDtypeStruct((B, KH, P, G, D), jnp.float32),
            jax.ShapeDtypeStruct((B, KH, P, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, KH, P, G, 1), jnp.float32),
        ],
        interpret=interpret,
    )(page_table, lens, qg, kp, vp)
    return lse_merge(acc, m, l).reshape(B, H, D).astype(q.dtype)
