"""Unified decoder stack: one scan-over-layer-groups engine for every
decoder-only family in the zoo (dense, MoE, VLM cross-attn, Jamba hybrid,
RWKV).

A ``ModelConfig`` compiles to a *layer plan*: a list of per-position
descriptions for one group of ``cfg.group_size`` layers (the periodic
pattern — e.g. jamba's 1-attention-per-8 interleave, llama-vision's
1-cross-attn-per-5). Parameters for position j are stacked over the
``n_groups`` scan axis, so HLO size stays O(group) not O(layers).

Three entry points (all pure functions of (params, cfg, ...)):

    stack_specs(cfg)                          -> Spec tree
    forward_full(params, cfg, x, ...)         -> (hidden, cache)   prefill/train
    forward_step(params, cfg, x, cache, pos)  -> (hidden, cache)   decode

Cache layout: {"p{j}": per-layer cache pytree stacked [n_groups, ...]}.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import layers as L
from . import mamba as M
from . import rwkv6 as R
from .attention import pool_leaf_shape
from .common import constrain_batch, rms_norm
from .spec import Spec

Pytree = Any


@dataclass(frozen=True)
class PosPlan:
    mixer: str            # "attn" | "mamba" | "rwkv"
    ffn: str              # "mlp" | "moe" | "rwkv"
    cross: bool = False   # cross-attention sub-block after the mixer
    window: int = 0       # sliding window for attn mixers (0 = full)


def layer_plan(cfg) -> List[PosPlan]:
    """The periodic per-group layer pattern for this config."""
    plan = []
    for j in range(cfg.group_size):
        if cfg.attention_free:
            plan.append(PosPlan("rwkv", "rwkv"))
            continue
        mixer = "attn" if cfg.is_attn_layer(j) else "mamba"
        ffn = "moe" if cfg.is_moe_layer(j) else "mlp"
        cross = cfg.is_cross_attn_layer(j)
        plan.append(PosPlan(mixer, ffn, cross, cfg.sliding_window))
    return plan


# ---------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------

def _stack(tree: Pytree, n: int) -> Pytree:
    """Add the leading ("layers", n_groups) scan axis to every Spec."""
    if isinstance(tree, dict):
        return {k: _stack(v, n) for k, v in tree.items()}
    s: Spec = tree
    return Spec((n,) + s.shape, ("layers",) + s.axes, s.init, s.scale, s.dtype)


def _pos_specs(cfg, pos: PosPlan) -> Dict[str, Pytree]:
    d = cfg.d_model
    p: Dict[str, Pytree] = {"norm1": L.norm_spec(cfg)}
    if pos.mixer == "attn":
        p["attn"] = L.attn_specs(cfg)
    elif pos.mixer == "mamba":
        p["mamba"] = M.mamba_specs(cfg)
    else:  # rwkv: time-mix + channel-mix replace attn + ffn
        p["time"] = R.rwkv_time_specs(cfg)
        p["norm2"] = L.norm_spec(cfg)
        p["channel"] = R.rwkv_channel_specs(cfg)
        return p
    if pos.cross:
        p["cross_norm"] = L.norm_spec(cfg)
        p["cross"] = L.attn_specs(cfg, cross=True)
    if not cfg.parallel_block:
        p["norm2"] = L.norm_spec(cfg)
    if pos.ffn == "moe":
        if cfg.moe_impl == "halfexpert":
            from .moe_a2a import moe_halfexpert_specs
            p["ffn"] = moe_halfexpert_specs(cfg, cfg.moe_tp)
        else:
            p["ffn"] = L.moe_specs(cfg)
    else:
        p["ffn"] = L.mlp_specs(cfg)
    return p


def stack_specs(cfg) -> Dict[str, Pytree]:
    plan = layer_plan(cfg)
    return {f"p{j}": _stack(_pos_specs(cfg, pos), cfg.n_groups)
            for j, pos in enumerate(plan)}


# ---------------------------------------------------------------------
# full-sequence forward (train / prefill)
# ---------------------------------------------------------------------

def _pos_full(p, cfg, pos: PosPlan, x, kv_src, want_cache: bool,
              attn_impl: str, in_cache=None, causal: bool = True):
    """One layer position, full sequence. Returns (x, cache | {})."""
    cache: Dict[str, Any] = {}
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if pos.mixer == "rwkv":
        y, c = R.rwkv_time_full(p["time"], cfg, h,
                                cache=in_cache and
                                {"state": in_cache["state"],
                                 "shift": in_cache["shift"]})
        x = x + y
        cache.update(c)
        h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
        y2, c2 = R.rwkv_channel_full(p["channel"], cfg, h2,
                                     cache=in_cache and
                                     {"shift_c": in_cache["shift_c"]})
        x = x + y2
        cache.update(c2)
        return x, (cache if want_cache else {})
    if pos.mixer == "attn":
        y, kv = L.attn_full(p["attn"], cfg, h, causal=causal,
                            window=pos.window, impl=attn_impl,
                            return_cache=want_cache)
        if want_cache:
            W = pos.window
            S = kv["k"].shape[1]
            if W and W < S:
                # keep the last W positions, ring-aligned: token t -> slot t%W
                kv = {n: jnp.roll(a[:, S - W:], (S - W) % W, axis=1)
                      for n, a in kv.items()}
            cache["k"], cache["v"] = kv["k"], kv["v"]
    else:  # mamba
        y, c = M.mamba_full(p["mamba"], cfg, h, cache=in_cache and
                            {"conv": in_cache["conv"],
                             "ssm": in_cache["ssm"]})
        cache.update(c)
    if cfg.parallel_block:
        y2 = L.mlp_full(p["ffn"], cfg, h)      # same pre-norm (cohere-style)
        x = x + y + y2
        return x, (cache if want_cache else {})
    x = x + y
    if pos.cross:
        hc = rms_norm(x, p["cross_norm"], cfg.norm_eps)
        yc, ckv = L.cross_attn_full(p["cross"], cfg, hc, kv_src,
                                    impl=attn_impl)
        x = x + yc
        if want_cache:
            cache["ck"], cache["cv"] = ckv["k"], ckv["v"]
    h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
    if pos.ffn == "moe":
        if cfg.moe_impl == "halfexpert":
            from ..launch.sharding import ambient_mesh
            from .moe_a2a import moe_halfexpert
            x = x + moe_halfexpert(p["ffn"], cfg, h2, ambient_mesh())
        else:
            x = x + L.moe_full(p["ffn"], cfg, h2)
    else:
        x = x + L.mlp_full(p["ffn"], cfg, h2)
    return x, (cache if want_cache else {})


def forward_full(params, cfg, x, *, kv_src=None, want_cache: bool = False,
                 attn_impl: str = "auto", remat: bool = False,
                 in_cache=None, causal: bool = True
                 ) -> Tuple[jax.Array, Optional[Pytree]]:
    """x: [B, S, d] embedded inputs -> (hidden [B, S, d], cache | None).

    ``kv_src``: [B, Skv, d] cross-attention source (vision/encoder states).
    ``in_cache``: continue from a previous recurrent state (mamba/rwkv
    chunked prefill); attention positions are NOT resumable this way.
    """
    plan = layer_plan(cfg)

    def group_body(carry, xs):
        x = constrain_batch(carry)
        gp, gc = xs
        caches = {}
        for j, pos in enumerate(plan):
            x, c = _pos_full(gp[f"p{j}"], cfg, pos, x, kv_src, want_cache,
                             attn_impl,
                             in_cache=gc.get(f"p{j}") if gc else None,
                             causal=causal)
            caches[f"p{j}"] = c
        return constrain_batch(x), caches

    if in_cache is None:
        def no_cache_body(c, gp):
            return group_body(c, (gp, None))
        body = jax.checkpoint(no_cache_body) if remat else no_cache_body
        hidden, caches = jax.lax.scan(body, x, params)
    else:
        body = jax.checkpoint(group_body) if remat else group_body
        hidden, caches = jax.lax.scan(body, x, (params, in_cache))
    return hidden, (caches if want_cache else None)


# ---------------------------------------------------------------------
# single-token decode step
# ---------------------------------------------------------------------

def _mlp_step(pf, x_dtype, h):
    """Inline SwiGLU for the [B, d] step paths."""
    g = jax.nn.silu(jnp.einsum("bd,df->bf", h, pf["wg"])
                    .astype(jnp.float32))
    u = jnp.einsum("bd,df->bf", h, pf["wu"]).astype(jnp.float32)
    return jnp.einsum("bf,fd->bd", (g * u).astype(x_dtype), pf["wd"])


def _ffn_step_tail(p, cfg, pos: PosPlan, x):
    """norm2 + FFN after the mixer residual — shared by the dense and
    paged single-token step paths."""
    h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
    if pos.ffn == "moe":
        from .common import ep_decode
        if ep_decode():
            # capacity dispatch with S=1 (cap = K: exact, dropless).
            # When the expert dim is SHARDED (jamba: 16e on 16-way
            # model), gather-based moe_step would all-gather whole
            # expert tensors per step (measured 56GiB on jamba); the
            # dispatch form keeps experts parallel and moves only
            # token activations.
            return x + L.moe_full(p["ffn"], cfg, h2[:, None])[:, 0]
        # experts replicated / ff-sharded (mixtral, grok: 8e on a
        # 16-way axis): per-token weight slicing is shard-local,
        # and dispatch's E/K x overcompute would cost more
        # (measured 2.3x step regression on mixtral decode).
        return x + L.moe_step(p["ffn"], cfg, h2)
    return x + _mlp_step(p["ffn"], x.dtype, h2)


def _pos_step(p, cfg, pos: PosPlan, x, cache, position):
    new: Dict[str, Any] = {}
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if pos.mixer == "rwkv":
        y, c = R.rwkv_time_step(p["time"], cfg, h,
                                {"state": cache["state"],
                                 "shift": cache["shift"]})
        x = x + y
        new.update(c)
        h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
        y2, c2 = R.rwkv_channel_step(p["channel"], cfg, h2,
                                     {"shift_c": cache["shift_c"]})
        x = x + y2
        new.update(c2)
        return x, new
    if pos.mixer == "attn":
        y, kv = L.attn_step(p["attn"], cfg, h,
                            {"k": cache["k"], "v": cache["v"]},
                            position, window=pos.window)
        new["k"], new["v"] = kv["k"], kv["v"]
    else:
        y, c = M.mamba_step(p["mamba"], cfg, h,
                            {"conv": cache["conv"], "ssm": cache["ssm"]})
        new.update(c)
    if cfg.parallel_block:
        return x + y + _mlp_step(p["ffn"], x.dtype, h), new
    x = x + y
    if pos.cross:
        hc = rms_norm(x, p["cross_norm"], cfg.norm_eps)
        yc, _ = L.cross_attn_step(p["cross"], cfg, hc,
                                  {"k": cache["ck"], "v": cache["cv"]})
        x = x + yc
        new["ck"], new["cv"] = cache["ck"], cache["cv"]
    return _ffn_step_tail(p, cfg, pos, x), new


def _pos_step_paged(p, cfg, pos: PosPlan, x, pages, page_table, position):
    """One attention layer position, single-token decode against the
    shared page pool. The FFN tail is the dense step's."""
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    y, pages = L.attn_step_paged(p["attn"], cfg, h, pages, page_table,
                                 position)
    if cfg.parallel_block:
        return x + y + _mlp_step(p["ffn"], x.dtype, h), pages
    return _ffn_step_tail(p, cfg, pos, x + y), pages


def forward_step(params, cfg, x, cache, position
                 ) -> Tuple[jax.Array, Pytree]:
    """x: [B, d] one embedded token; cache from forward_full/cache_specs.
    ``position``: scalar int32 context length so far. Returns (hidden,
    updated cache) — caller donates the cache buffer."""
    plan = layer_plan(cfg)

    def group_body(x, xs):
        x = constrain_batch(x)
        gp, gc = xs
        new = {}
        for j, pos in enumerate(plan):
            x, c = _pos_step(gp[f"p{j}"], cfg, pos, x, gc[f"p{j}"], position)
            new[f"p{j}"] = c
        return constrain_batch(x), new

    hidden, new_cache = jax.lax.scan(group_body, x, (params, cache))
    return hidden, new_cache


def forward_step_paged(params, cfg, x, pages, page_table, position
                       ) -> Tuple[jax.Array, Pytree]:
    """x: [B, d] one embedded token per batch lane; ``pages`` is the
    instance-wide KV page pool {"p{j}": {"g{g}": {"k","v": [n_pages,
    KH, R, 128]}}} (``paged_cache_specs``; caller donates the buffers);
    ``page_table``: [B, P] page ids; ``position``: [B] int32 context
    lengths. Returns (hidden, updated pool). Attention-only stacks —
    see paged_cache_specs.

    Unlike the dense step, the layer loop is UNROLLED rather than
    scanned: scanning over the pool would slice each group's pages in
    (and stack them back out) every iteration — a full pool copy per
    step, exactly the traffic paging exists to avoid. Unrolled, every
    pool leaf flows through one scatter + one gather, so XLA aliases
    the donated buffers in place; HLO grows O(n_layers), acceptable for
    a serving step."""
    plan = layer_plan(cfg)
    new = {pj: dict(groups) for pj, groups in pages.items()}
    for g in range(cfg.n_groups):
        x = constrain_batch(x)
        for j, pos in enumerate(plan):
            gp = jax.tree.map(lambda a: a[g], params[f"p{j}"])
            x, c = _pos_step_paged(gp, cfg, pos, x, new[f"p{j}"][f"g{g}"],
                                   page_table, position)
            new[f"p{j}"][f"g{g}"] = c
    return constrain_batch(x), new


def _pos_mixed_paged(p, cfg, pos_plan: PosPlan, xc, xd, pages, chunk_table,
                     chunk_start, chunk_len, dec_table, dec_pos):
    """One attention layer position over a mixed ragged batch: xc
    [Lc, C, d] padded prefill chunks, xd [Ld, d] decode lanes. One
    fused scatter+attend per layer; the FFN tails are the chunk
    (extend) and single-token (step) tails respectively."""
    hc = rms_norm(xc, p["norm1"], cfg.norm_eps)
    hd = rms_norm(xd, p["norm1"], cfg.norm_eps)
    yc, yd, pages = L.attn_mixed_paged(p["attn"], cfg, hc, hd, pages,
                                       chunk_table, chunk_start, chunk_len,
                                       dec_table, dec_pos)
    if cfg.parallel_block:
        return (xc + yc + L.mlp_full(p["ffn"], cfg, hc),
                xd + yd + _mlp_step(p["ffn"], xd.dtype, hd), pages)
    return (_ffn_extend_tail(p, cfg, pos_plan, xc + yc),
            _ffn_step_tail(p, cfg, pos_plan, xd + yd), pages)


def forward_mixed_paged(params, cfg, xc, xd, pages, chunk_table,
                        chunk_start, chunk_len, dec_table, dec_pos
                        ) -> Tuple[jax.Array, jax.Array, Pytree]:
    """Fused ragged iteration: every query token of the scheduling step
    in ONE forward. ``xc`` [Lc, C, d] embeds all prefill chunks padded
    to a bucketed common length C (lane l: chunk_len[l] real tokens
    from absolute position chunk_start[l]); ``xd`` [Ld, d] embeds all
    decode lanes (fed token at context position dec_pos[l]). Lanes
    address the pool through their page-table rows; padding lanes carry
    all-scratch rows. ``pages`` is the instance-wide pool as in
    forward_step_paged (caller donates; unrolled for the same in-place
    aliasing reason). Returns (hidden_c [Lc, C, d], hidden_d [Ld, d],
    updated pool).

    Decode-only and single-chunk batches are special cases of this
    entry, so one trace per (Lc, C, Ld) bucket triple serves any mix of
    phases — model dispatches per iteration stay O(1) in the number of
    active prefills.

    SPMD contract (DESIGN.md §13): this body is written once and runs
    unchanged on a tensor-parallel submesh. The engine jits it with the
    pool pinned to ``pool_pspec`` shardings and params TP-sharded by
    ``serve_policy``; GSPMD then partitions the page gathers/scatters
    and inserts the attention/MLP collectives. Nothing here may assume
    a device count — page-table indexing is position-based, so it is
    valid under head-, slot-, or page-sharded pools alike.

    Speculative contract (DESIGN.md §14): a chunk lane may be a VERIFY
    lane — K+1 drafted tokens mid-decode rather than a prefill chunk.
    Nothing here distinguishes the two: the lane scatters its K+1 KV
    entries positionally (overwriting any rejected junk a previous
    speculative step left there) and the causal extend mask hides
    positions past ``chunk_start + chunk_len``, which is exactly why
    rejected target-side tails need no trim."""
    plan = layer_plan(cfg)
    new = {pj: dict(groups) for pj, groups in pages.items()}
    for g in range(cfg.n_groups):
        xc, xd = constrain_batch(xc), constrain_batch(xd)
        for j, pos in enumerate(plan):
            gp = jax.tree.map(lambda a: a[g], params[f"p{j}"])
            xc, xd, c = _pos_mixed_paged(
                gp, cfg, pos, xc, xd, new[f"p{j}"][f"g{g}"], chunk_table,
                chunk_start, chunk_len, dec_table, dec_pos)
            new[f"p{j}"][f"g{g}"] = c
    return constrain_batch(xc), constrain_batch(xd), new


# ---------------------------------------------------------------------
# chunked-prefill extension (engine continuous batching)
# ---------------------------------------------------------------------

def _pos_extend(p, cfg, pos: PosPlan, x, cache, start):
    """One layer position over a chunk x [B, C, d] against a linear cache.
    SWA windows are honored as masks (the engine uses linear, non-ring
    buffers sized to its max context)."""
    new: Dict[str, Any] = {}
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    if pos.mixer == "rwkv":
        y, c = R.rwkv_time_full(p["time"], cfg, h,
                                cache={"state": cache["state"],
                                       "shift": cache["shift"]})
        x = x + y
        new.update(c)
        h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
        y2, c2 = R.rwkv_channel_full(p["channel"], cfg, h2,
                                     cache={"shift_c": cache["shift_c"]})
        x = x + y2
        new.update(c2)
        return x, new
    if pos.mixer == "attn":
        y, kv = L.attn_extend(p["attn"], cfg, h,
                              {"k": cache["k"], "v": cache["v"]},
                              start, window=pos.window)
        new["k"], new["v"] = kv["k"], kv["v"]
    else:
        y, c = M.mamba_full(p["mamba"], cfg, h,
                            cache={"conv": cache["conv"],
                                   "ssm": cache["ssm"]})
        new.update(c)
    if cfg.parallel_block:
        return x + y + L.mlp_full(p["ffn"], cfg, h), new
    x = x + y
    if pos.cross:
        hc = rms_norm(x, p["cross_norm"], cfg.norm_eps)
        yc, _ = L.cross_attn_extend(p["cross"], cfg, hc,
                                    {"k": cache["ck"], "v": cache["cv"]})
        x = x + yc
        new["ck"], new["cv"] = cache["ck"], cache["cv"]
    return _ffn_extend_tail(p, cfg, pos, x), new


def _ffn_extend_tail(p, cfg, pos: PosPlan, x):
    """norm2 + FFN over a chunk — shared by dense and paged extend."""
    h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
    if pos.ffn == "moe":
        return x + L.moe_extend(p["ffn"], cfg, h2)  # dropless: chunk == full
    return x + L.mlp_full(p["ffn"], cfg, h2)


def _pos_extend_paged(p, cfg, pos: PosPlan, x, pages, page_table, start):
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    y, pages = L.attn_extend_paged(p["attn"], cfg, h, pages, page_table,
                                   start)
    if cfg.parallel_block:
        return x + y + L.mlp_full(p["ffn"], cfg, h), pages
    return _ffn_extend_tail(p, cfg, pos, x + y), pages


def seed_cross_cache(params, cfg, kv_src, cache) -> Pytree:
    """Compute per-layer cross-attention KV from ``kv_src`` [B, Skv, d]
    and write it into the cache's ck/cv slots (extend-mode admission of
    a VLM request: the vision tokens arrive once, before text chunks)."""
    plan = layer_plan(cfg)

    def body(_, xs):
        gp, gc = xs
        new = {}
        for j, pos in enumerate(plan):
            c = dict(gc[f"p{j}"])
            if pos.cross:
                c["ck"] = jnp.einsum("...d,dhk->...hk", kv_src,
                                     gp[f"p{j}"]["cross"]["wk"])
                c["cv"] = jnp.einsum("...d,dhk->...hk", kv_src,
                                     gp[f"p{j}"]["cross"]["wv"])
            new[f"p{j}"] = c
        return 0, new

    _, cache = jax.lax.scan(body, 0, (params, cache))
    return cache


def forward_extend(params, cfg, x, cache, start) -> Tuple[jax.Array, Pytree]:
    """Chunked prefill: x [B, C, d] new embedded tokens at absolute start
    position(s) ``start`` (scalar or [B]); cache buffers are linear and
    must be allocated large enough (engine: max context). Returns
    (hidden [B, C, d], updated cache)."""
    plan = layer_plan(cfg)

    def group_body(x, xs):
        gp, gc = xs
        new = {}
        for j, pos in enumerate(plan):
            x, c = _pos_extend(gp[f"p{j}"], cfg, pos, x, gc[f"p{j}"], start)
            new[f"p{j}"] = c
        return x, new

    hidden, new_cache = jax.lax.scan(group_body, x, (params, cache))
    return hidden, new_cache


def forward_extend_paged(params, cfg, x, pages, page_table, start
                         ) -> Tuple[jax.Array, Pytree]:
    """Chunked prefill against the page pool: x [B, C, d] new embedded
    tokens at absolute start position(s) ``start``; pages/page_table as
    in forward_step_paged (unrolled for the same aliasing reason).
    Returns (hidden [B, C, d], updated pool)."""
    plan = layer_plan(cfg)
    new = {pj: dict(groups) for pj, groups in pages.items()}
    for g in range(cfg.n_groups):
        for j, pos in enumerate(plan):
            gp = jax.tree.map(lambda a: a[g], params[f"p{j}"])
            x, c = _pos_extend_paged(gp, cfg, pos, x, new[f"p{j}"][f"g{g}"],
                                     page_table, start)
            new[f"p{j}"][f"g{g}"] = c
    return x, new


# ---------------------------------------------------------------------
# cache specs (abstract, for dry-run and engine allocation)
# ---------------------------------------------------------------------

def cache_specs(cfg, batch: int, seq: int) -> Pytree:
    """ShapeDtypeStructs of the decode cache for (batch, seq) context.
    Attention positions hold [G, B, S_c, KH, D] with S_c = min(seq, window
    or seq); recurrent positions hold their O(1) state."""
    plan = layer_plan(cfg)
    G = cfg.n_groups
    dt = jnp.dtype(cfg.dtype)
    out = {}

    def stackG(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((G,) + s.shape, s.dtype), tree)

    for j, pos in enumerate(plan):
        if pos.mixer == "rwkv":
            out[f"p{j}"] = stackG(R.rwkv_cache_spec(cfg, batch))
        elif pos.mixer == "mamba":
            c = M.mamba_cache_spec(cfg, batch)
            if pos.cross:
                raise NotImplementedError
            out[f"p{j}"] = stackG(c)
        else:
            S_c = min(seq, pos.window) if pos.window else seq
            c = {"k": jax.ShapeDtypeStruct(
                     (batch, S_c, cfg.n_kv_heads, cfg.head_dim), dt),
                 "v": jax.ShapeDtypeStruct(
                     (batch, S_c, cfg.n_kv_heads, cfg.head_dim), dt)}
            if pos.cross:
                c["ck"] = jax.ShapeDtypeStruct(
                    (batch, cfg.n_vision_tokens, cfg.n_kv_heads,
                     cfg.head_dim), dt)
                c["cv"] = c["ck"]
            out[f"p{j}"] = stackG(c)
    return out


def paged_servable(cfg) -> bool:
    """True when every layer position can be served from the KV page
    pool: self-attention mixers only, no cross-attention, no sliding
    window, decoder-only. Recurrent/hybrid/VLM stacks use the dense
    reference path (snapshot-granularity reuse, DESIGN.md §5)."""
    if cfg.encoder_decoder:
        return False
    return all(p.mixer == "attn" and not p.cross and not p.window
               for p in layer_plan(cfg))


def paged_cache_specs(cfg, n_pages: int, page_size: int) -> Pytree:
    """ShapeDtypeStructs of the per-layer KV page pools: one k/v pair
    per (attention position, scan group) — i.e. per physical layer —
    each leaf [n_pages, KH, R, 128] in the paged kernel's block layout
    (``attention.pool_leaf_shape``, DESIGN.md §2). The pool is
    instance-wide: requests address it through page tables, so there
    is no batch or seq dim, and leaves are kept per-layer (not stacked
    over groups) so the unrolled paged forwards update them in place.
    On a serve submesh each leaf shards by ``launch.sharding.pool_pspec``
    (heads when divisible, else a page's rows), so every chip holds a
    1/tp slice of EVERY page — aggregate pool capacity scales with the
    submesh."""
    if not paged_servable(cfg):
        raise ValueError(f"{cfg.name}: stack is not paged-servable")
    plan = layer_plan(cfg)
    dt = jnp.dtype(cfg.dtype)
    s = jax.ShapeDtypeStruct(
        pool_leaf_shape(n_pages, page_size, cfg.n_kv_heads, cfg.head_dim),
        dt)
    return {f"p{j}": {f"g{g}": {"k": s, "v": s}
                      for g in range(cfg.n_groups)}
            for j, _pos in enumerate(plan)}


def cache_bytes(cfg, batch: int, seq: int) -> int:
    total = 0
    for leaf in jax.tree.leaves(cache_specs(cfg, batch, seq)):
        n = leaf.dtype.itemsize
        for d in leaf.shape:
            n *= d
        total += n
    return total
