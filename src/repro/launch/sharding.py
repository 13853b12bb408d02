"""Divisibility-aware sharding policy: logical axes -> PartitionSpecs.

Every parameter Spec carries logical axis names (models/spec.py); this
module maps them onto mesh axes through ordered preference lists. An
axis candidate is taken only if (a) every mesh axis in it exists, (b)
the dim size divides the combined mesh-axis size, and (c) none of its
mesh axes are already used by another dim of the same tensor. Otherwise
the next preference is tried; an exhausted list replicates the dim.

Policies:
  train  — TP over "model" (heads/ff/vocab/experts/inner) + FSDP over
           "data" on the embed dim; "pod" is pure DP (gradient reduce
           only crosses pods).
  serve  — TP over "model"; models whose TP shard would still exceed
           ``fsdp_bytes_per_chip`` also FSDP the embed dim (XLA then
           all-gathers one layer at a time inside the scan).
  KV cache (decode) — batch over DP axes, sequence over "model"
           (distributed flash-decoding: softmax partials psum over the
           sequence shards); if batch can't shard (long-context B=1),
           the sequence takes every axis.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.spec import Spec, _walk
from .mesh import TP_AXIS, axis_size, data_axes

Pytree = Any
AxisPref = Union[str, Tuple[str, ...]]


def _norm(pref: AxisPref) -> Tuple[str, ...]:
    return (pref,) if isinstance(pref, str) else tuple(pref)


@dataclass(frozen=True)
class Policy:
    rules: Dict[str, Tuple[AxisPref, ...]]

    def pspec(self, spec: Spec, mesh: Mesh) -> P:
        used: set = set()
        out: List[Optional[Union[str, Tuple[str, ...]]]] = []
        for dim, name in zip(spec.shape, spec.axes):
            picked = None
            for pref in self.rules.get(name, ()):  # type: ignore[arg-type]
                axes = _norm(pref)
                if not all(a in mesh.shape for a in axes):
                    continue
                if any(a in used for a in axes):
                    continue
                if dim % axis_size(mesh, axes) != 0:
                    continue
                picked = axes[0] if len(axes) == 1 else tuple(axes)
                used.update(axes)
                break
            out.append(picked)
        while out and out[-1] is None:
            out.pop()
        return P(*out)


def train_policy(mesh: Mesh) -> Policy:
    return Policy(rules={
        "vocab": ("model",),
        "heads": ("model",),
        "kv_heads": ("model",),          # KH=8 vs 16 -> replicated (GQA)
        "ff": ("model",),
        # "expert" exists only on the expert-axis mesh (it6)
        "experts": ("expert", "model"),
        # expert weights: FSDP goes on the ff dim jointly with TP, NEVER
        # on the input dim (a data-sharded contraction dim turns the
        # expert matmuls into 20GiB fp32 partial-sum all-reduces)
        "expert_ff": (("model", "data"), ("model",), ("data",)),
        "expert_in": (),
        # halfexpert MoE (shard_map EP): one half-expert per model
        # column, its ff columns FSDP'd over data
        "halfexpert": ("model",),
        "expert_ff_fsdp": ("data",),
        "inner": ("model",),
        "embed": ("data",),              # FSDP
    })


def serve_policy(mesh: Mesh, param_bytes: int,
                 fsdp_bytes_per_chip: int = 6 << 30) -> Policy:
    tp = axis_size(mesh, "model")
    big = param_bytes // tp > fsdp_bytes_per_chip
    rules = {
        "vocab": ("model",),
        "heads": ("model",),
        "kv_heads": ("model",),
        "ff": ("model",),
        "experts": ("model",),
        "expert_ff": ((("model", "data"), ("model",), ("data",))
                      if big else (("model",),)),
        "expert_in": (),
        "halfexpert": ("model",),
        "expert_ff_fsdp": (("data",) if big else ()),
        "inner": ("model",),
    }
    if big:
        rules["embed"] = ("data",)       # weight shard must go 2D
    return Policy(rules=rules)


# ---------------------------------------------------------------------
# parameter / state shardings
# ---------------------------------------------------------------------

def param_shardings(specs: Pytree, mesh: Mesh, policy: Policy) -> Pytree:
    return _walk(specs, lambda _, s: NamedSharding(mesh,
                                                   policy.pspec(s, mesh)))


def param_pspecs(specs: Pytree, mesh: Mesh, policy: Policy) -> Pytree:
    return _walk(specs, lambda _, s: policy.pspec(s, mesh))


def like_tree(template: Pytree, target: Pytree) -> Pytree:
    """Map a spec-tree-derived sharding tree onto a same-structure tree
    (e.g. optimizer moments mirror the param shardings)."""
    return jax.tree.map(lambda _, s: s, target, template)


# ---------------------------------------------------------------------
# activation / batch shardings
# ---------------------------------------------------------------------

def dp_spec(mesh: Mesh, batch: int) -> Optional[Union[str, Tuple[str, ...]]]:
    """Mesh axes for a batch dim (pod+data when divisible, else data,
    else replicate)."""
    cands = [data_axes(mesh), ("data",)]
    for axes in cands:
        if axes and all(a in mesh.shape for a in axes) \
                and batch % axis_size(mesh, axes) == 0:
            return axes[0] if len(axes) == 1 else tuple(axes)
    return None


def batch_shardings(batch_specs: Dict[str, jax.ShapeDtypeStruct],
                    mesh: Mesh) -> Dict[str, NamedSharding]:
    out = {}
    for name, s in batch_specs.items():
        if s.shape == ():
            out[name] = NamedSharding(mesh, P())
            continue
        bspec = dp_spec(mesh, s.shape[0])
        rest = [None] * (len(s.shape) - 1)
        out[name] = NamedSharding(mesh, P(bspec, *rest))
    return out


# ---------------------------------------------------------------------
# KV / state cache shardings (decode cells)
# ---------------------------------------------------------------------

_SEQ_PREFS = (("pod", "data", "model"), ("data", "model"), ("model",),
              ("data",))


def _cache_pspec(name: str, shape: Tuple[int, ...], mesh: Mesh,
                 used_batch: bool = True) -> P:
    """Leaf-name-aware cache sharding. Shapes:
      k/v/ck/cv : [G, B, S, KH, D]
      conv      : [G, B, W, ed]      ssm: [G, B, ed, N]
      state     : [G, B, H, Dh, Dh]  shift/shift_c: [G, B, d]
    """
    used: set = set()
    B = shape[1]
    bspec = dp_spec(mesh, B)
    if bspec is not None:
        used.update(_norm(bspec))
    if name in ("k", "v", "ck", "cv"):
        S, KH = shape[2], shape[3]
        # head-wise TP first (Megatron-style: each chip owns KH/tp
        # heads, zero cross-chip traffic inside attention) — but ONLY
        # when the TP degree divides kv_heads. The GQA edge (tp > KH,
        # or non-divisible KH) must REPLICATE heads and fall back to
        # sequence sharding: an indivisible head spec is a compile
        # error, not a slow path.
        hspec = None
        if "model" in mesh.shape and "model" not in used \
                and KH % axis_size(mesh, "model") == 0 \
                and axis_size(mesh, "model") > 1:
            hspec = "model"
            used.add("model")
        sspec = None
        for axes in _SEQ_PREFS:
            if all(a in mesh.shape for a in axes) \
                    and not (set(axes) & used) \
                    and S % axis_size(mesh, axes) == 0:
                sspec = axes[0] if len(axes) == 1 else tuple(axes)
                break
        return P(None, bspec, sspec, hspec, None)
    if name == "conv":
        ed = shape[3]
        m = "model" if ed % axis_size(mesh, "model") == 0 else None
        return P(None, bspec, None, m)
    if name == "ssm":
        ed = shape[2]
        m = "model" if ed % axis_size(mesh, "model") == 0 else None
        return P(None, bspec, m, None)
    if name == "state":
        H = shape[2]
        m = "model" if H % axis_size(mesh, "model") == 0 else None
        return P(None, bspec, m, None, None)
    # shift / shift_c / anything else: batch-sharded only
    return P(None, bspec, *([None] * (len(shape) - 2)))


def cache_shardings(cache_specs: Pytree, mesh: Mesh) -> Pytree:
    def walk(tree):
        if isinstance(tree, dict):
            return {k: (NamedSharding(mesh, _cache_pspec(k, v.shape, mesh))
                        if not isinstance(v, dict) else walk(v))
                    for k, v in tree.items()}
        raise TypeError(tree)
    return walk(cache_specs)


# ---------------------------------------------------------------------
# paged KV pool shardings (serving data plane, DESIGN.md §13)
# ---------------------------------------------------------------------

def pool_pspec(shape: Tuple[int, ...], mesh: Mesh) -> P:
    """PartitionSpec for one paged-pool leaf [n_pages, KH, R, 128]
    (``models.attention.pool_leaf_shape``).

    Preference order, each guarded by divisibility:
      1. head-wise   P(None, "model", None, None) — each chip owns
         KH/tp kv-heads of every page (Megatron attention, zero
         resharding inside the kernel);
      2. row-wise    P(None, None, "model", None) — the GQA fallback:
         when the TP degree exceeds (or doesn't divide) kv_heads, heads
         REPLICATE and each chip owns R/tp rows, i.e. PS/tp token slots,
         of every page (sequence sharding at page granularity —
         distributed flash-decoding over the slot shards);
      3. page-wise   P("model", None, None, None) — last resort when
         the rows don't divide either;
      4. replicate.

    The guard in step 1 is the serve-time GQA edge: producing an
    indivisible head spec (e.g. KH=1 pools on a 4-chip submesh) would
    be a mesh compile error, so heads replicate and the sequence axis
    takes the shard instead."""
    n_pages, kh, rows, _lanes = shape
    if TP_AXIS not in mesh.shape:
        return P(None, None, None, None)
    tp = axis_size(mesh, TP_AXIS)
    if tp <= 1:
        return P(None, None, None, None)
    if kh % tp == 0:
        return P(None, TP_AXIS, None, None)
    if rows % tp == 0:
        return P(None, None, TP_AXIS, None)
    if n_pages % tp == 0:
        return P(TP_AXIS, None, None, None)
    return P(None, None, None, None)


def span_pspec(shape: Tuple[int, ...], mesh: Mesh) -> P:
    """PartitionSpec for token-major KV with heads at axis -2 — the
    host<->device DMA payloads (restore/prefetch scatters ship pages
    laid out token-major, [N, PS, KH, D]). Only the head shard carries
    over from ``pool_pspec``: each chip moves exactly its own kv-head
    slice (per-shard DMA); row/page-sharded pools replicate the payload
    and let the scatter route rows to their shards."""
    kh = shape[-2]
    if TP_AXIS not in mesh.shape:
        return P(*([None] * len(shape)))
    tp = axis_size(mesh, TP_AXIS)
    if tp > 1 and kh % tp == 0:
        return P(*([None] * (len(shape) - 2)), TP_AXIS, None)
    return P(*([None] * len(shape)))


def pool_shardings(pool_specs: Pytree, mesh: Mesh) -> Pytree:
    """NamedShardings for the engine's paged pool pytree
    ({pj: {gg: {"k"/"v": [n_pages, KH, R, 128]}}})."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, pool_pspec(s.shape, mesh)),
        pool_specs)


def span_shardings(pool_specs: Pytree, mesh: Mesh) -> Pytree:
    """NamedShardings for the restore payloads matching the pool tree:
    leaf [N, PS, KH, D] per pool leaf [n_pages, KH, R, 128]; only the
    pool's head count (axis 1) decides the spec."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, span_pspec((1, 1, s.shape[1], 1),
                                                 mesh)),
        pool_specs)


def mesh_scope(mesh: Optional[Mesh]):
    """Context for tracing a sharded step: ``mesh`` in context as the
    abstract mesh, where model code that needs shard_map finds it
    (``ambient_mesh``: the paged kernel per head shard, the halfexpert
    MoE). A no-op without a mesh."""
    if mesh is None:
        return contextlib.nullcontext()
    return jax.sharding.use_abstract_mesh(mesh.abstract_mesh)


def ambient_mesh():
    """The mesh ``mesh_scope`` put in context, as an abstract mesh;
    None outside one."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def placed_zeros(specs: Pytree, shardings: Any) -> Pytree:
    """Zero arrays shaped like ``specs``, created directly where
    ``shardings`` (one sharding, or a tree of them) place them — a
    multi-GiB KV pool never lands whole on the default device first,
    as ``device_put(jnp.zeros(...))`` would make it."""
    return jax.jit(
        lambda: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs),
        out_shardings=shardings)()
