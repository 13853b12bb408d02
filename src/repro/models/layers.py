"""Layer modules: param specs + forward functions for every layer kind.

Each layer kind exposes
    <kind>_specs(cfg, ...) -> Spec tree
    <kind>_full(p, cfg, x, ...)    full-sequence forward (train / prefill);
                                   returns (y, cache_entry | None)
    <kind>_step(p, cfg, x, cache_entry, pos) -> (y, new_cache_entry)
                                   single-token decode against a cache.

Shapes: x is [B, S, d] for full, [B, d] for step.  Cache entries are
per-layer pytrees; the stack in transformer.py stacks them over the
scan ("layers") axis.

Logical sharding axes are declared on every Spec (see models/spec.py);
launch/sharding.py turns them into PartitionSpecs.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import (attention, decode_attention, extend_attention,
                        gather_tokens, mixed_paged_attention,
                        paged_attention, write_runs)
from .common import (constrain_batch, constrain_moe_dispatch, rms_norm,
                     rope)
from .spec import Spec

Pytree = Any


# =====================================================================
# GQA attention (self- or cross-)
# =====================================================================

def attn_specs(cfg, cross: bool = False) -> Dict[str, Spec]:
    """Projection weights are stored head-FACTORED [d, H, Dh] (not fused
    [d, H*Dh]) so the "heads" logical axis is the head-count dim — TP
    sharding is then head-aligned by construction and the attention
    einsums never force a resharding. KV heads (GQA, usually 8 < TP
    degree) are replicated Megatron-style (the policy maps "kv_heads"
    to no mesh axis when indivisible)."""
    d = cfg.d_model
    # fan-in scaling by the contracted dims: d_model into q/k/v, and
    # heads x head_dim into the output (``init="fan_in"`` reads only
    # shape[-2], which is the head count / head_dim for these factored
    # shapes and would saturate the attention softmax)
    w_in = d ** -0.5
    w_out = (cfg.n_heads * cfg.head_dim) ** -0.5
    return {
        "wq": Spec((d, cfg.n_heads, cfg.head_dim),
                   ("embed", "heads", None), scale=w_in),
        "wk": Spec((d, cfg.n_kv_heads, cfg.head_dim),
                   ("embed", "kv_heads", None), scale=w_in),
        "wv": Spec((d, cfg.n_kv_heads, cfg.head_dim),
                   ("embed", "kv_heads", None), scale=w_in),
        "wo": Spec((cfg.n_heads, cfg.head_dim, d),
                   ("heads", None, "embed"), scale=w_out),
    }


def _project_qkv(p, cfg, x):
    q = jnp.einsum("...d,dhk->...hk", x, p["wq"])
    k = jnp.einsum("...d,dhk->...hk", x, p["wk"])
    v = jnp.einsum("...d,dhk->...hk", x, p["wv"])
    return q, k, v


def attn_full(p, cfg, x, *, causal: bool = True, positions=None,
              window: int = 0, impl: str = "auto",
              return_cache: bool = True):
    """Full-seq self-attention. Returns (y, {"k","v"} | None)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x)
    if positions is None:
        positions = jnp.arange(S)[None, :]
    if not cfg.attention_free and cfg.rope_theta:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    o = attention(q, k, v, causal=causal, window=window, impl=impl)
    y = jnp.einsum("...hk,hkd->...d", o, p["wo"])
    cache = {"k": k, "v": v} if return_cache else None
    return y, cache


def cross_attn_full(p, cfg, x, kv_src, *, impl: str = "auto",
                    precomputed: Optional[Dict[str, jax.Array]] = None):
    """Cross-attention: queries from x [B,S,d], keys/values from kv_src
    [B,Skv,d] (or reuse ``precomputed`` {"k","v"}). No RoPE, not causal."""
    B, S, _ = x.shape
    q = jnp.einsum("...d,dhk->...hk", x, p["wq"])
    if precomputed is not None:
        k, v = precomputed["k"], precomputed["v"]
    else:
        k = jnp.einsum("...d,dhk->...hk", kv_src, p["wk"])
        v = jnp.einsum("...d,dhk->...hk", kv_src, p["wv"])
    o = attention(q, k, v, causal=False, impl=impl)
    y = jnp.einsum("...hk,hkd->...d", o, p["wo"])
    return y, {"k": k, "v": v}


def attn_step(p, cfg, x, cache, pos, *, window: int = 0):
    """Decode one token. x: [B, d]; cache {"k","v"}: [B, S, KH, D].

    ``pos`` is the context length so far — a scalar int32 (uniform batch,
    the dry-run decode cells) or a [B] vector (the engine's continuous
    batching, where every request sits at a different depth). The new KV
    is written at ring-buffer slot pos % S (S == window for SWA layers),
    and attention masks to the valid window.
    Returns (y, new_cache).
    """
    B, d = x.shape
    S = cache["k"].shape[1]
    q = jnp.einsum("bd,dhk->bhk", x, p["wq"])
    k = jnp.einsum("bd,dhk->bhk", x, p["wk"])
    v = jnp.einsum("bd,dhk->bhk", x, p["wv"])
    pos = jnp.asarray(pos)
    if cfg.rope_theta:
        posb = jnp.full((B, 1), pos) if pos.ndim == 0 else pos[:, None]
        q = rope(q[:, None], posb, cfg.rope_theta)[:, 0]
        k = rope(k[:, None], posb, cfg.rope_theta)[:, 0]
    slot = pos % S  # ring buffer (S == full seq for dense; window for SWA)
    if pos.ndim == 0:
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k[:, None].astype(cache["k"].dtype), (0, slot, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], v[:, None].astype(cache["v"].dtype), (0, slot, 0, 0))
    else:
        bidx = jnp.arange(B)
        k_cache = cache["k"].at[bidx, slot].set(k.astype(cache["k"].dtype))
        v_cache = cache["v"].at[bidx, slot].set(v.astype(cache["v"].dtype))
    cache_len = jnp.minimum(pos + 1, S)
    if window and window < S:
        cache_len = jnp.minimum(pos + 1, window)
    # ring semantics: when pos+1 <= S the buffer is position-aligned and the
    # plain causal mask is exact. When wrapped, positions are rotated; since
    # every slot then holds a token inside the window, mask = all valid.
    o = decode_attention(q, k_cache, v_cache, cache_len)
    y = jnp.einsum("bhk,hkd->bd", o, p["wo"])
    return y, {"k": k_cache, "v": v_cache}


def attn_step_paged(p, cfg, x, pages, page_table, pos):
    """Decode one token against the shared page pool (no per-request
    cache buffer). x: [B, d]; pages {"k","v"}: leaves [n_pages, KH, R,
    LANES] (``attention.pool_leaf_shape``) — the POOL, shared by every
    request on the instance; page_table: [B, P] page ids; pos: [B]
    context length so far (the fed token's absolute position). The new
    KV is written in place into page page_table[b, pos//PS] at token
    offset pos % PS (``attention.write_runs``); the pages written by
    different batch lanes are guaranteed distinct by the host-side
    allocator (shared pages are CoW'd before a sequence may write).
    Returns (y, new pages)."""
    B, d = x.shape
    q = jnp.einsum("bd,dhk->bhk", x, p["wq"])
    k = jnp.einsum("bd,dhk->bhk", x, p["wk"])
    v = jnp.einsum("bd,dhk->bhk", x, p["wv"])
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        pos = jnp.full((B,), pos)
    if cfg.rope_theta:
        q = rope(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]
        k = rope(k[:, None], pos[:, None], cfg.rope_theta)[:, 0]
    one = jnp.ones_like(pos)
    k_pages = write_runs(pages["k"], page_table, pos, one, k[:, None])
    v_pages = write_runs(pages["v"], page_table, pos, one, v[:, None])
    o = paged_attention(q, k_pages, v_pages, page_table, pos + 1)
    y = jnp.einsum("bhk,hkd->bd", o, p["wo"])
    return y, {"k": k_pages, "v": v_pages}


def attn_mixed_paged(p, cfg, xc, xd, pages, chunk_table, chunk_start,
                     chunk_len, dec_table, dec_pos):
    """Fused ragged iteration against the page pool: ONE scatter+attend
    for every query token of the step. xc [Lc, C, d] packs all prefill
    chunks padded to C (lane l holds chunk_len[l] real tokens starting
    at absolute position chunk_start[l]); xd [Ld, d] packs all decode
    lanes (fed token at position dec_pos[l]). KV for both halves is
    scattered through the lanes' page tables before either half
    attends, then attention runs per half (extend-style for chunks,
    decode-style for single-token lanes — mixed_paged_attention).

    Chunk-pad tokens (beyond chunk_len) write nothing, and window pages
    a chunk does not reach go to the scratch page (``write_runs``): a
    full table row's clip-clamped tail entry would otherwise point
    garbage writes at the lane's own live pages. Padding LANES must
    carry all-scratch table rows with start/pos 0.
    Returns (yc [Lc, C, d], yd [Ld, d], new pages)."""
    C = xc.shape[1]
    qc, kc, vc = _project_qkv(p, cfg, xc)
    qd = jnp.einsum("bd,dhk->bhk", xd, p["wq"])
    kd = jnp.einsum("bd,dhk->bhk", xd, p["wk"])
    vd = jnp.einsum("bd,dhk->bhk", xd, p["wv"])
    cpos = chunk_start[:, None] + jnp.arange(C)[None, :]        # [Lc, C]
    if cfg.rope_theta:
        qc = rope(qc, cpos, cfg.rope_theta)
        kc = rope(kc, cpos, cfg.rope_theta)
        qd = rope(qd[:, None], dec_pos[:, None], cfg.rope_theta)[:, 0]
        kd = rope(kd[:, None], dec_pos[:, None], cfg.rope_theta)[:, 0]
    one = jnp.ones_like(dec_pos)
    k_pages = write_runs(pages["k"], chunk_table, chunk_start, chunk_len,
                         kc)
    v_pages = write_runs(pages["v"], chunk_table, chunk_start, chunk_len,
                         vc)
    k_pages = write_runs(k_pages, dec_table, dec_pos, one, kd[:, None])
    v_pages = write_runs(v_pages, dec_table, dec_pos, one, vd[:, None])
    oc, od = mixed_paged_attention(qc, qd, k_pages, v_pages, chunk_table,
                                   chunk_start, dec_table, dec_pos)
    yc = jnp.einsum("...hk,hkd->...d", oc, p["wo"])
    yd = jnp.einsum("bhk,hkd->bd", od, p["wo"])
    return yc, yd, {"k": k_pages, "v": v_pages}


def attn_extend_paged(p, cfg, x, pages, page_table, start):
    """Chunked-prefill extension against the page pool: x [B, C, d] new
    tokens at absolute position ``start`` (scalar or [B]); the chunk's
    KV is scattered into the table's pages, then chunk queries attend
    to the gathered table rows. Returns (y [B, C, d], new pages)."""
    B, C, d = x.shape
    q, k, v = _project_qkv(p, cfg, x)
    start = jnp.asarray(start)
    positions = jnp.broadcast_to(
        (start[:, None] if start.ndim else start)
        + jnp.arange(C)[None, :], (B, C))
    if cfg.rope_theta:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    full = jnp.full((B,), C, jnp.int32)
    k_pages = write_runs(pages["k"], page_table, positions[:, 0], full, k)
    v_pages = write_runs(pages["v"], page_table, positions[:, 0], full, v)
    kc = gather_tokens(k_pages, page_table, cfg.head_dim)
    vc = gather_tokens(v_pages, page_table, cfg.head_dim)
    o = extend_attention(q, kc, vc, start, start + C)
    y = jnp.einsum("...hk,hkd->...d", o, p["wo"])
    return y, {"k": k_pages, "v": v_pages}


def attn_extend(p, cfg, x, cache, start, *, window: int = 0):
    """Chunked-prefill extension: x [B, C, d] new tokens starting at
    absolute position ``start`` (scalar or [B]); cache {"k","v"} is a
    linear (non-ring) [B, S, KH, D] buffer with the first ``start``
    positions already valid. Returns (y [B, C, d], new cache)."""
    B, C, d = x.shape
    S = cache["k"].shape[1]
    q, k, v = _project_qkv(p, cfg, x)
    start = jnp.asarray(start)
    positions = (start + jnp.arange(C)[None, :] if start.ndim
                 else (start + jnp.arange(C))[None, :])
    if cfg.rope_theta:
        if start.ndim:
            positions = start[:, None] + jnp.arange(C)[None, :]
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    if start.ndim == 0:
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, start, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, start, 0, 0))
    else:
        bidx = jnp.arange(B)[:, None]
        cols = start[:, None] + jnp.arange(C)[None, :]
        k_cache = cache["k"].at[bidx, cols].set(k.astype(cache["k"].dtype))
        v_cache = cache["v"].at[bidx, cols].set(v.astype(cache["v"].dtype))
    kv_len = start + C
    o = extend_attention(q, k_cache, v_cache, start, kv_len, window=window)
    y = jnp.einsum("...hk,hkd->...d", o, p["wo"])
    return y, {"k": k_cache, "v": v_cache}


def cross_attn_extend(p, cfg, x, cache):
    """Chunked-prefill cross-attention against fixed precomputed KV."""
    B, C, d = x.shape
    q = jnp.einsum("...d,dhk->...hk", x, p["wq"])
    o = attention(q, cache["k"], cache["v"], causal=False, impl="naive")
    y = jnp.einsum("...hk,hkd->...d", o, p["wo"])
    return y, cache


def cross_attn_step(p, cfg, x, cache):
    """Decode-step cross-attention against fixed precomputed cross KV."""
    B, d = x.shape
    q = jnp.einsum("bd,dhk->bhk", x, p["wq"])
    S = cache["k"].shape[1]
    o = decode_attention(q, cache["k"], cache["v"], S)
    y = jnp.einsum("bhk,hkd->bd", o, p["wo"])
    return y, cache


# =====================================================================
# Dense FFN (SwiGLU)
# =====================================================================

def mlp_specs(cfg) -> Dict[str, Spec]:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "wg": Spec((d, f), ("embed", "ff"), init="fan_in"),
        "wu": Spec((d, f), ("embed", "ff"), init="fan_in"),
        "wd": Spec((f, d), ("ff", "embed"), init="fan_in"),
    }


def mlp_full(p, cfg, x):
    g = jax.nn.silu(jnp.einsum("...d,df->...f", x, p["wg"])
                    .astype(jnp.float32))
    u = jnp.einsum("...d,df->...f", x, p["wu"]).astype(jnp.float32)
    return jnp.einsum("...f,fd->...d", (g * u).astype(x.dtype), p["wd"])


# =====================================================================
# MoE FFN (top-k router, capacity-based dispatch)
# =====================================================================

def moe_specs(cfg) -> Dict[str, Spec]:
    """Expert weights use dedicated logical axes: FSDP must NOT land on
    the expert input dim ("embed") — contracting a data-sharded dim
    turns the expert matmuls into partial-sums and XLA all-reduces the
    fp32 dispatch-buffer-sized outputs (measured 20GiB per layer on
    grok). Instead "expert_ff" takes (model, data) jointly: weights stay
    fully sharded and XLA inserts per-layer weight all-gathers (FSDP
    semantics) at 1/34th the wire bytes."""
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": Spec((d, E), ("embed", None), init="fan_in",
                       dtype="float32"),
        "wg": Spec((E, d, f), ("experts", "expert_in", "expert_ff"),
                   init="fan_in"),
        "wu": Spec((E, d, f), ("experts", "expert_in", "expert_ff"),
                   init="fan_in"),
        "wd": Spec((E, f, d), ("experts", "expert_ff", "expert_in"),
                   init="fan_in"),
    }


def moe_full(p, cfg, x):
    """Capacity-based top-k dispatch with PER-SEQUENCE capacity groups.

    x: [B, S, d] -> [B, S, d]. Capacity is allocated per (sequence,
    expert) — cap = 1.25*S*K/E slots — so the dispatch cumsum runs along
    S only and every dispatch tensor keeps the batch dim, which shards
    over the data axes (a global-cumsum formulation would serialize the
    whole 1M-token batch through one unsharded buffer). Expert weights
    shard over "experts" when the count divides the model axis (jamba
    16e) and over "ff" otherwise (mixtral/grok 8e)."""
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.experts_per_token
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)                       # [B, S, E]
    topw, tope = jax.lax.top_k(gates, K)                          # [B, S, K]
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    cap = max(int(cfg.capacity_factor * S * K / E), K)
    onehot = jax.nn.one_hot(tope, E, dtype=jnp.int32)             # [B, S, K, E]
    flat = onehot.reshape(B, S * K, E)
    pos_in_exp = jnp.cumsum(flat, axis=1) - flat                  # [B, S*K, E]
    pos = (pos_in_exp * flat).sum(-1)                             # [B, S*K]
    keep = (pos < cap)
    weight = topw.reshape(B, S * K) * keep                        # drop overflow

    # dispatch: [B, E, cap, d]. Constrain batch-sharded / d-replicated
    # so the scatter stays shard-local (no SPMD fallback all-reduces).
    e_idx = tope.reshape(B, S * K)
    c_idx = jnp.minimum(pos, cap - 1)
    src = constrain_batch(jnp.repeat(x, K, axis=1)
                          * keep[..., None].astype(x.dtype))      # [B, S*K, d]
    disp = jnp.zeros((B, E, cap, d), dtype=x.dtype)
    bidx = jnp.arange(B)[:, None]
    disp = constrain_moe_dispatch(disp.at[bidx, e_idx, c_idx]
                                  .add(src.astype(x.dtype)))

    g = jax.nn.silu(jnp.einsum("becd,edf->becf", disp, p["wg"])
                    .astype(jnp.float32))
    u = jnp.einsum("becd,edf->becf", disp, p["wu"]).astype(jnp.float32)
    eo = jnp.einsum("becf,efd->becd", (g * u).astype(x.dtype), p["wd"])
    eo = constrain_moe_dispatch(eo)

    # combine
    out = eo[bidx, e_idx, c_idx] * weight[..., None].astype(x.dtype)
    out = out.reshape(B, S, K, d).sum(2)
    return constrain_batch(out.astype(x.dtype))


def moe_extend(p, cfg, x):
    """Dropless MoE for chunked-prefill extension: x [B, C, d].

    The capacity-based ``moe_full`` drops overflow tokens as a function
    of the whole batch, so chunked execution would diverge from one-shot
    prefill. Engine chunks are small, so the exact gather-based dispatch
    is affordable; the large-scale training path keeps ``moe_full``."""
    B, C, d = x.shape
    out = moe_step(p, cfg, x.reshape(B * C, d))
    return out.reshape(B, C, d)


def moe_step(p, cfg, x):
    """Decode-step MoE: x [B, d]. Small batch — dense-compute all experts
    is wasteful; use gather-based per-token dispatch instead."""
    B, d = x.shape
    E, K = cfg.n_experts, cfg.experts_per_token
    logits = jnp.einsum("bd,de->be", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)
    topw, tope = jax.lax.top_k(gates, K)                          # [B, K]
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    wg = p["wg"][tope]                                            # [B, K, d, f]
    wu = p["wu"][tope]
    wd = p["wd"][tope]                                            # [B, K, f, d]
    g = jax.nn.silu(jnp.einsum("bd,bkdf->bkf", x, wg).astype(jnp.float32))
    u = jnp.einsum("bd,bkdf->bkf", x, wu).astype(jnp.float32)
    o = jnp.einsum("bkf,bkfd->bkd", (g * u).astype(x.dtype), wd)
    return (o * topw[..., None].astype(x.dtype)).sum(1)


# =====================================================================
# Embeddings / head
# =====================================================================

def embed_specs(cfg) -> Dict[str, Spec]:
    s: Dict[str, Spec] = {
        "tok": Spec((cfg.vocab_size, cfg.d_model), ("vocab", "embed")),
        "final_norm": Spec((cfg.d_model,), (None,), init="ones"),
    }
    if not cfg.tie_embeddings:
        s["head"] = Spec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"),
                         init="fan_in")
    return s


def embed_tokens(p, cfg, tokens):
    return jnp.take(p["tok"], tokens, axis=0)


def head_matrix(p, cfg):
    return p["tok"].T if cfg.tie_embeddings else p["head"]


def norm_spec(cfg) -> Spec:
    return Spec((cfg.d_model,), (None,), init="ones")
