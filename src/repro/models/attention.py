"""Attention implementations with three interchangeable backends.

  naive      — materializes [Sq, Skv] scores; tiny smoke tests only.
  blockwise  — flash-style online softmax via lax.scan over KV blocks;
               O(S * kv_block) score memory; what the dry-run lowers.
               Causal masking is block-masked (off-diagonal blocks are
               computed then masked — ~2x attention FLOPs for causal
               prefill; see EXPERIMENTS.md §Perf for the two-phase
               triangular optimization that removes this).
  banded     — sliding-window attention as a diagonal-band block scan:
               per scan step every q block pairs with kv block (qi - o),
               gathered with jnp.take. FLOPs ~ S * (window + block).
  pallas     — TPU kernel (src/repro/kernels); engines select it on TPU.

All functions take q:[B,Sq,H,D], k/v:[B,Skv,KH,D] with GQA group
G = H // KH, and return [B,Sq,H,D].
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _gqa_split(q, n_kv):
    B, Sq, H, D = q.shape
    G = H // n_kv
    return q.reshape(B, Sq, n_kv, G, D), G


def naive_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset=0, kv_len: Optional[jax.Array] = None):
    B, Sq, H, D = q.shape
    KH = k.shape[2]
    qg, G = _gqa_split(q, KH)
    scale = D ** -0.5
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(k.shape[1])
    mask = jnp.ones((Sq, k.shape[1]), dtype=bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    if kv_len is not None:
        mask = mask[None] & (k_pos[None, None, :] < kv_len[:, None, None])
        scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    else:
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def blockwise_attention(q, k, v, *, causal: bool = True,
                        q_block: int = 512, kv_block: int = 1024,
                        window: int = 0, q_offset: int = 0):
    """Flash-style attention: scan over KV blocks with online softmax.

    Score memory per step: [B, Sq, H, kv_block] fp32 — independent of Skv.

    GQA is handled by REPEATING the (replicated, small) KV heads up to H
    rather than reshaping q to [KH, G, ...]: the TP policy shards q on
    the head axis (e.g. 96 heads / 16 chips), and a [KH=8, G] reshape of
    that sharded axis is never shard-aligned — it would force an
    all-gather of the 32k-long q. The repeat keeps every tensor sharded
    on the same head axis; XLA fuses the gather into the einsum.
    """
    from .common import constrain_batch, constrain_heads
    B, Sq, H, D = q.shape
    Skv, KH = k.shape[1], k.shape[2]
    G = H // KH
    q = constrain_heads(q)
    if G > 1:
        # replicate the SMALL pre-repeat KV explicitly: any cross-chip
        # gather then moves KH heads, not H (G-times less wire); the
        # repeat itself becomes shard-local
        k = constrain_heads(jnp.repeat(constrain_batch(k), G, axis=2))
        v = constrain_heads(jnp.repeat(constrain_batch(v), G, axis=2))
    if Skv % kv_block:
        pad = kv_block - Skv % kv_block
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nkv = k.shape[1] // kv_block
    scale = D ** -0.5
    kb = k.reshape(B, nkv, kv_block, H, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nkv, kv_block, H, D).transpose(1, 0, 2, 3, 4)
    q_pos = q_offset + jnp.arange(Sq)

    def body(carry, xs):
        m, l, acc = carry
        j, kj, vj = xs
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kj).astype(jnp.float32) * scale
        k_pos = j * kv_block + jnp.arange(kv_block)
        mask = k_pos[None, :] < Skv
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # guard fully-masked rows (m_new == NEG_INF) against NaNs
        m_safe = jnp.maximum(m_new, -1e29)
        p = jnp.exp(s - m_safe[..., None])
        corr = jnp.exp(m - m_safe)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(q.dtype),
                        vj).astype(jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Sq), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((B, H, Sq), dtype=jnp.float32)
    a0 = jnp.zeros((B, H, Sq, D), dtype=jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (jnp.arange(nkv), kb, vb))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def banded_attention(q, k, v, *, window: int, q_block: int = 1024,
                     q_offset: int = 0):
    """Sliding-window causal attention as a diagonal-band scan.

    q is split into blocks; at scan step o every q block qi attends kv
    block (qi - o). Steps needed: ceil(window / q_block) + 1, so FLOPs are
    ~ S * (window + q_block) instead of S^2. Requires q and kv aligned
    (Sq == Skv, q_offset == 0) — the prefill case SWA needs.
    """
    B, S, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    assert k.shape[1] == S and q_offset == 0, "banded path needs aligned q/kv"
    if G > 1:        # repeat-KV GQA (see blockwise_attention)
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    blk = min(q_block, S)
    if S % blk:
        raise ValueError(f"seq {S} not divisible by block {blk}")
    nb = S // blk
    qb = q.reshape(B, nb, blk, H, D)
    kb = k.reshape(B, nb, blk, H, D)
    vb = v.reshape(B, nb, blk, H, D)
    scale = D ** -0.5
    n_steps = min(window // blk + 2, nb)
    q_pos_in = jnp.arange(blk)

    def body(carry, o):
        m, l, acc = carry
        idx = jnp.maximum(jnp.arange(nb) - o, 0)            # kv block per q block
        kj = jnp.take(kb, idx, axis=1)                      # [B, nb, blk, H, D]
        vj = jnp.take(vb, idx, axis=1)
        s = jnp.einsum("bnqhd,bnkhd->bnhqk", qb, kj).astype(jnp.float32) * scale
        q_pos = (jnp.arange(nb)[:, None] * blk + q_pos_in[None, :])  # [nb, blk]
        k_pos = idx[:, None] * blk + q_pos_in[None, :]               # [nb, blk]
        mask = (k_pos[:, None, :] <= q_pos[:, :, None])
        mask &= k_pos[:, None, :] > q_pos[:, :, None] - window
        valid = (jnp.arange(nb) - o >= 0)[:, None, None]
        mask &= valid
        s = jnp.where(mask[None, :, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        m_safe = jnp.maximum(m_new, -1e29)
        p = jnp.exp(s - m_safe[..., None])
        corr = jnp.exp(m - m_safe)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bnhqk,bnkhd->bnhqd", p.astype(q.dtype),
                        vj).astype(jnp.float32)
        return (m_new, l_new, acc * corr[..., None] + pv), None

    m0 = jnp.full((B, nb, H, blk), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((B, nb, H, blk), dtype=jnp.float32)
    a0 = jnp.zeros((B, nb, H, blk, D), dtype=jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(n_steps))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.transpose(0, 1, 3, 2, 4).reshape(B, S, H, D)
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *, window: int = 0):
    """Single-position decode: q [B, H, D] against cache [B, S, KH, D].

    Linear in S; scores [B, H, S] fp32 are small per chip once batch/heads
    are sharded. ``cache_len`` is a scalar (uniform context length across
    the batch — the decode_32k / long_500k cells) or a [B] vector.
    """
    B, H, D = q.shape
    S, KH = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    qg = q.reshape(B, KH, G, D)
    scale = D ** -0.5
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache).astype(jnp.float32) * scale
    k_pos = jnp.arange(S)
    clen = jnp.asarray(cache_len)
    # the query sits at position (clen - 1): it sees k_pos in
    # [clen - window, clen) for SWA, [0, clen) otherwise.
    if clen.ndim == 0:
        mask = k_pos < clen
        if window:
            mask &= k_pos >= clen - window
        mask = mask[None, None, None, :]
    else:
        mask = k_pos[None, :] < clen[:, None]
        if window:
            mask &= k_pos[None, :] >= (clen[:, None] - window)
        mask = mask[:, None, None, :]
    s = jnp.where(mask, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", w.astype(q.dtype), v_cache)
    return out.reshape(B, H, D)


# ---------------------------------------------------------------------
# page-pool layout (DESIGN.md §2)
# ---------------------------------------------------------------------

LANES = 128     # the TPU's vector width: the minor dimension of a pool leaf


def pool_rows(page_size: int, head_dim: int) -> int:
    """Rows of LANES values that one (page, kv-head) block spans: its
    page_size x head_dim values, token-major, LANES to a row."""
    if LANES % head_dim and head_dim % LANES:
        raise ValueError(f"head_dim {head_dim} neither divides nor is a "
                         f"multiple of {LANES}")
    if page_size * head_dim % LANES:
        raise ValueError(f"a page of {page_size} tokens x {head_dim} does "
                         f"not fill whole rows of {LANES}")
    return page_size * head_dim // LANES


def pool_leaf_shape(n_pages: int, page_size: int, kv_heads: int,
                    head_dim: int) -> Tuple[int, int, int, int]:
    """Shape of one pool leaf: [n_pages, KH, R, LANES] with R =
    page_size * head_dim / LANES. Token t of a page starts at row
    (t * D) // LANES, lane (t * D) % LANES of its head's block: D = 64
    packs two tokens a row, D = 128 is plain [n_pages, KH, PS, D]."""
    return (n_pages, kv_heads, pool_rows(page_size, head_dim), LANES)


def pages_to_tokens(pages, head_dim: int):
    """Pool pages [..., N, KH, R, LANES] -> token-major [..., N * PS,
    KH, D] (jax or numpy arrays)."""
    *lead, n, kh, rows, lanes = pages.shape
    ps = rows * lanes // head_dim
    x = pages.reshape(*lead, n, kh, ps, head_dim).swapaxes(-3, -2)
    return x.reshape(*lead, n * ps, kh, head_dim)


def tokens_to_pages(tokens, page_size: int):
    """Token-major [..., N * PS, KH, D] -> pool pages [..., N, KH, R,
    LANES]: the inverse of ``pages_to_tokens``."""
    *lead, t, kh, d = tokens.shape
    n = t // page_size
    x = tokens.reshape(*lead, n, page_size, kh, d).swapaxes(-3, -2)
    return x.reshape(*lead, n, kh, pool_rows(page_size, d), LANES)


def gather_tokens(leaf, table, head_dim: int):
    """The pages of page-table rows ``table`` [B, P], token-major
    [B, P * PS, KH, D]: reads those pages only, never the pool."""
    return pages_to_tokens(leaf[table], head_dim)


def write_pages(leaf, pids, mask, vals):
    """Write token KV ``vals`` [N, PS, KH, D] into pool pages ``pids``
    [N] where ``mask`` [N, PS] is set, keeping the rest of each page:
    one gather of the N pages, a select, and one scatter of whole
    [KH, R, LANES] blocks, which XLA applies in place to a donated leaf
    (a token-granular scatter into the packed rows would not be). Pages
    in ``pids`` must be distinct, except the scratch page, whose content
    is garbage."""
    ps, d = vals.shape[1], vals.shape[-1]
    old = pages_to_tokens(leaf[pids], d).reshape(vals.shape)
    new = jnp.where(mask[..., None, None], vals.astype(leaf.dtype), old)
    return leaf.at[pids].set(tokens_to_pages(new.reshape(-1, *new.shape[2:]),
                                             ps))


def write_runs(leaf, table, start, length, vals):
    """Write runs of tokens through page tables: ``vals`` [B, C, KH, D]
    holds lane b's tokens at positions start[b] + t of table row
    ``table`` [B, P], for t < length[b] (later tokens are padding and
    write nothing). Each lane touches the W pages its run can span;
    a window page the run does not reach is sent to the scratch page
    0, so padding lanes (length 0, all-scratch rows) write only there."""
    B, C = vals.shape[:2]
    ps = leaf.shape[2] * LANES // vals.shape[-1]         # tokens a page
    W = -(-(C + ps - 1) // ps)
    start = jnp.asarray(start, jnp.int32).reshape(B)
    length = jnp.asarray(length, jnp.int32).reshape(B)
    first = start // ps
    t = first[:, None] * ps + jnp.arange(W * ps)[None, :] - start[:, None]
    mask = (t >= 0) & (t < length[:, None])                     # [B, W*PS]
    x = jnp.take_along_axis(vals, jnp.clip(t, 0, C - 1)[..., None, None],
                            axis=1)
    wp = jnp.clip(first[:, None] + jnp.arange(W)[None, :], 0,
                  table.shape[1] - 1)
    mask = mask.reshape(B * W, ps)
    pids = jnp.where(mask.any(-1), jnp.take_along_axis(table, wp, axis=1)
                     .reshape(-1), 0)
    return write_pages(leaf, pids, mask,
                       x.reshape(B * W, ps, *vals.shape[2:]))


def auto_paged_impl() -> str:
    """The paged-decode implementation 'auto' resolves to: the Pallas
    kernel on a TPU backend, the jnp gather elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "gather"


def paged_attention(q, k_pages, v_pages, page_table, lens, *,
                    impl: str = "auto"):
    """Decode attention over a shared KV page pool.

    q: [B, H, D]; k/v_pages: pool leaves [n_pages, KH, R, LANES]
    (``pool_leaf_shape`` — the pool is shared across every request on
    the instance); page_table: [B, P] int32 page ids (entries past a
    request's length may point anywhere, they are masked); lens: [B]
    valid token counts. Returns [B, H, D].

    'pallas' streams pages HBM->VMEM via the page-table-prefetched
    kernel (kernels/paged_attention.py), reading the leaves as stored;
    'gather' is the jnp reference — a per-request gather of the table
    rows followed by masked dense decode attention. 'auto' picks pallas
    on TPU, gather elsewhere (interpret-mode pallas unrolls the page
    grid and is far slower than one fused gather+softmax on CPU).
    """
    if impl == "auto":
        impl = auto_paged_impl()
    if impl == "pallas":
        return _paged_kernel(q, k_pages, v_pages, page_table, lens)
    D = q.shape[-1]
    k = gather_tokens(k_pages, page_table, D)
    v = gather_tokens(v_pages, page_table, D)
    return decode_attention(q, k, v, lens)


def _paged_kernel(q, k_pages, v_pages, page_table, lens):
    """The Pallas paged-decode kernel. GSPMD cannot partition a Mosaic
    kernel, so on a tensor-parallel submesh (the mesh the engine puts
    in context with ``sharding.mesh_scope``) it runs per head shard
    under shard_map: heads are independent, and the pool is
    head-sharded whenever the TP degree divides the KV heads. A
    row-sharded pool (TP degree above the KV heads) takes the gather
    path, which GSPMD partitions."""
    from ..kernels.paged_attention import paged_decode_attention
    from ..launch.sharding import TP_AXIS, ambient_mesh
    mesh = ambient_mesh()
    tp = (1 if mesh is None or TP_AXIS not in mesh.axis_names
          else mesh.shape[TP_AXIS])
    if tp == 1:
        return paged_decode_attention(q, k_pages, v_pages, page_table, lens)
    if k_pages.shape[1] % tp:
        return paged_attention(q, k_pages, v_pages, page_table, lens,
                               impl="gather")
    heads, pool = P(None, TP_AXIS, None), P(None, TP_AXIS, None, None)
    return jax.shard_map(paged_decode_attention, mesh=mesh,
                         in_specs=(heads, pool, pool, P(), P()),
                         out_specs=heads, check_vma=False)(
        q, k_pages, v_pages, page_table, lens)


def mixed_paged_attention(qc, qd, k_pages, v_pages, chunk_table,
                          chunk_start, dec_table, dec_pos):
    """Ragged mixed prefill+decode attention over a shared KV page pool.

    One fused call for a scheduling step's whole mixed batch. The
    ragged token set is split into two uniform halves so each lane's
    KV is gathered from the pool exactly ONCE (a flat per-token
    formulation would re-gather a chunk lane's KV C times):

      * chunk half — qc [Lc, C, H, D]: all prefill chunks, padded to a
        common bucketed length C; lane l's first query sits at absolute
        position chunk_start[l]. Runs as extend_attention against the
        lane's gathered table rows — the role the shared-prefix
        (Hydragen) kernel plays on TPU.
      * decode half — qd [Ld, H, D]: all single-token decode lanes,
        the fed token at context position dec_pos[l]. Runs as
        paged/decode attention masked to dec_pos + 1 — the half the
        Pallas paged-decode kernel serves on TPU.

    Both halves read pool state AFTER the caller scattered this step's
    new KV, so intra-chunk causality and cross-half isolation both fall
    out of absolute-position masks (lanes never share writable pages —
    the host allocator CoWs shared pages before a sequence may write).
    Padding lanes must carry an all-scratch (page 0) table row with
    start/pos 0; their outputs are garbage and dropped by the caller.

    Returns (oc [Lc, C, H, D], od [Ld, H, D]).
    """
    C, D = qc.shape[1], qc.shape[-1]
    kc = gather_tokens(k_pages, chunk_table, D)
    vc = gather_tokens(v_pages, chunk_table, D)
    # kv_len = start + C is safe for padded lanes/tokens: the causal
    # mask (k_pos <= q_pos) already bounds every REAL query, and padded
    # queries' outputs are dropped.
    oc = extend_attention(qc, kc, vc, chunk_start, chunk_start + C)
    od = paged_attention(qd, k_pages, v_pages, dec_table, dec_pos + 1)
    return oc, od


def extend_attention(q, k_cache, v_cache, start, kv_len, *, window: int = 0):
    """Chunked-prefill attention: new queries against a partially-filled
    cache. q: [B, C, H, D] (chunk of C new tokens whose first token sits
    at absolute position ``start``); caches: [B, S, KH, D] already
    containing the new chunk's KV; ``kv_len`` = start + C (valid cache
    prefix). ``start``/``kv_len`` may be scalars or [B] vectors.

    Materializes [B, H, C, S] scores — intended for the engine's short
    chunks, not for 32k prefill (the blockwise path covers that)."""
    B, C, H, D = q.shape
    S, KH = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    qg = q.reshape(B, C, KH, G, D)
    scale = D ** -0.5
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    start = jnp.asarray(start)
    kv_len = jnp.asarray(kv_len)
    if start.ndim == 0:
        start = jnp.full((B,), start)
    if kv_len.ndim == 0:
        kv_len = jnp.full((B,), kv_len)
    q_pos = start[:, None] + jnp.arange(C)[None, :]          # [B, C]
    k_pos = jnp.arange(S)[None, None, :]                     # [1, 1, S]
    mask = k_pos <= q_pos[..., None]
    if window:
        mask &= k_pos > q_pos[..., None] - window
    mask &= k_pos < kv_len[:, None, None]
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, v_cache.astype(jnp.float32))
    return out.reshape(B, C, H, D).astype(q.dtype)


def attention(q, k, v, *, causal=True, window=0, q_offset=0,
              impl: str = "auto", q_block=512, kv_block=1024):
    """Backend dispatch. 'auto': naive for tiny, banded for SWA, else blockwise."""
    S = max(q.shape[1], k.shape[1])
    if impl == "auto":
        if S <= 1024:
            impl = "naive"
        elif window and q.shape[1] == k.shape[1] and q_offset == 0 \
                and q.shape[1] % min(q_block, q.shape[1]) == 0:
            impl = "banded"
        else:
            impl = "blockwise"
    if impl == "naive":
        return naive_attention(q, k, v, causal=causal, window=window,
                               q_offset=q_offset)
    if impl == "banded":
        return banded_attention(q, k, v, window=window, q_block=q_block,
                                q_offset=q_offset)
    if impl == "blockwise":
        return blockwise_attention(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset, q_block=q_block,
                                   kv_block=min(kv_block, k.shape[1]))
    raise ValueError(f"unknown attention impl {impl!r}")
