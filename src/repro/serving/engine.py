"""Per-instance continuous-batching engine with REAL JAX forwards.

Real prefill/decode math runs on the instance's chip (or its TP
submesh; reduced models on the CPU in tests) while the LocalScheduler
drives iteration-level scheduling (priority groups, chunked prefill,
LRU eviction).

Two data planes share the scheduling logic (DESIGN.md §2):

  * PAGED (default for attention-only stacks) — all KV lives in one
    device-resident page pool per layer ([n_pages, KH, R, 128], the
    paged kernel's block layout: models/attention.pool_leaf_shape);
    requests address it through page tables held by
    serving/kv_cache.py::PagedKVPool. Prefix reuse is ``fork()`` page
    aliasing with refcounts + copy-on-write — admission performs ZERO
    device KV copies (one page-granular CoW copy only when the reuse
    boundary is not page-aligned). Iterations with prefill work run
    FUSED: all ready prefill chunks and all decode slots packed into
    one flat ragged token batch and dispatched as a single donated jit
    (DESIGN.md §7) — dispatches/iteration are O(1) in the number of
    active prefills. Pure-decode iterations run the slot decode step
    (DESIGN.md §3): no per-iteration cache concat/index copies. Every
    dispatch pads to the configured lane and chunk maxima, so an engine
    runs one mixed and one decode program. Radix-tree nodes alias the
    pool through per-node page tables; eviction maps to
    ``release``/``trim`` (DESIGN.md §4).

  * DENSE (reference; recurrent/hybrid/VLM stacks) — per-request linear
    cache pytrees; cached attention-KV slabs are copied into a new
    request's cache, and batched decode rebuilds the batch cache with
    concat/index per iteration. Kept as the equivalence oracle for the
    paged path and as the only path for snapshot-granularity archs.

With ``EngineConfig.host_capacity_tokens > 0`` the paged plane grows a
second memory tier (DESIGN.md §8): eviction DEMOTES node KV device->
host (one batched gather per eviction plan into numpy spans keyed by
radix node) instead of dropping it, and a later prefix hit RESTORES it
into fresh pages — one batched scatter folded into the step's fused
dispatch — instead of recomputing the prefill. The local scheduler
owns the tier policy (host LRU + budget); serving/kv_offload.py holds
the bytes and moves them.

Reuse granularity (DESIGN.md §5):
  * attention KV      — token granularity (exact: KV depends only on the
                        token prefix; RoPE positions are absolute);
  * recurrent state   — snapshot granularity: the state after a full
    (mamba/rwkv)        prompt is stored at the radix leaf; a new request
                        reuses the longest snapshot boundary <= its
                        matched length and recomputes the remainder.

``launch/serve.py`` serves through this engine, one replica per chip
or one per TP submesh, with static dispatch shapes compiled at warm-up.
"""

from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from ..core.local_scheduler import Batch, LocalScheduler, LocalSchedulerConfig
from ..core.radix_tree import PathKey, PrefixSpan
from ..core.request import Request, RequestState
from ..launch import sharding as shard_lib
from ..launch.mesh import make_serve_mesh
from ..models import zoo, transformer as T
from ..models.attention import write_pages
from .batch import ForwardBatch, ModelWorkerBatch
from .faults import CircuitBreaker, InstanceCrashed
from .kv_cache import PagedKVPool
from .kv_offload import HostKVStore, PagedHostTier
from .speculative import DraftWorker, SpeculativeConfig
from .telemetry import HOST_SPANS, StatsDict, frac_of, host_span

Pytree = Any


class AdmissionError(RuntimeError):
    """A request the engine cannot serve (oversized for max_context).
    Distinct from ValueError so genuine defects in admission code are
    not silently converted into per-request aborts."""


@dataclass
class EngineConfig:
    instance_id: int = 0
    max_context: int = 256          # per-request context bound
    max_batch_requests: int = 8
    chunk_size: int = 32            # Sarathi chunk
    max_batch_tokens: int = 128
    capacity_tokens: int = 16384    # KV pool budget (tokens)
    page_size: int = 16
    priority_groups: int = 10
    fcfs: bool = False
    # None = auto: paged when the arch is paged-servable (attention-only
    # decoder stack), dense otherwise. True forces paged (raises if the
    # arch can't be paged-served); False forces the dense reference.
    paged: Optional[bool] = None
    # None = auto: on the paged plane, run FUSED ragged iterations —
    # every prefill chunk and decode slot of the step in one donated,
    # static-shape dispatch (DESIGN.md §7). False forces the PR-1 style
    # per-request prefill loop (kept as the fused plane's comparison
    # baseline in benchmarks/bench_engine.py). Ignored on dense.
    fused: Optional[bool] = None
    # Host-offload tier budget in tokens (DESIGN.md §8). 0 disables the
    # tier (eviction drops KV, the seed behavior). >0 — paged plane
    # only — eviction demotes node KV device->host and a later prefix
    # hit restores it into fresh pages instead of recomputing.
    host_capacity_tokens: int = 0
    # Speculative-restore budget (DESIGN.md §10; requires the host
    # tier). >0: waiting requests' host chains are scattered into node
    # pages by a second double-buffered DMA stream — issued before the
    # step's model dispatch, drained after it — so admission aliases
    # the prefetched pages and restores nothing on the TTFT path.
    prefetch_budget_tokens: int = 0
    # SPMD data plane (DESIGN.md §13): TP degree of this instance.
    # >1 makes the engine a tensor-parallel submesh — params sharded by
    # serve_policy, the paged pool by pool_pspec, the fused dispatch
    # compiled over the mesh. ``capacity_tokens`` stays PER-CHIP: the
    # pooled device KV capacity is capacity_tokens * chips (each chip
    # holds a 1/chips slice of every page, so aggregate HBM scales).
    chips_per_instance: int = 1
    # Fused speculative decoding (DESIGN.md §14). None (default)
    # disables it — the plane is byte-identical to the pre-spec engine.
    # A SpeculativeConfig attaches a DraftWorker (the draft model's own
    # paged plane) and turns every decode slot with >= 2 tokens of
    # headroom into a K+1-token verify chunk inside the SAME single
    # donated mixed dispatch, committing up to K+1 tokens per step with
    # greedy-exact outputs. Requires the fused paged plane.
    speculative: Optional[SpeculativeConfig] = None

    @property
    def device_capacity_tokens(self) -> int:
        """Aggregate KV token capacity of the instance's submesh."""
        return self.capacity_tokens * max(self.chips_per_instance, 1)


def _cache_zeros(specs: Pytree) -> Pytree:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs)


def _cache_concat(caches: List[Pytree]) -> Pytree:
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1), *caches)


def _cache_index(cache: Pytree, i: int) -> Pytree:
    return jax.tree.map(lambda x: x[:, i:i + 1], cache)


def _bucket(n: int) -> int:
    """Next power of two >= n: host-tier DMA batches pad to bucket
    sizes so their gathers/scatters retrace O(log n) times, not per
    size."""
    return 1 << max(n - 1, 0).bit_length()


class Engine:
    def __init__(self, cfg, params, econf: EngineConfig,
                 on_evict: Optional[Callable] = None,
                 devices: Optional[Sequence] = None):
        # the demo engine serves full attention; SWA only changes
        # semantics beyond max_context, which the demo never reaches
        self.model_cfg = dataclasses.replace(cfg, sliding_window=0)
        self.api = zoo.build(self.model_cfg)
        self.params = params
        self.econf = econf
        self.has_recurrent = any(
            p.mixer in ("mamba", "rwkv") for p in T.layer_plan(self.model_cfg))
        self.paged = (econf.paged if econf.paged is not None
                      else self.api.decode_paged is not None)
        if self.paged and self.api.decode_paged is None:
            raise ValueError(f"{cfg.name} is not paged-servable "
                             "(recurrent/cross/encdec positions)")
        self.fused = self.paged and (econf.fused is None or econf.fused)
        if econf.fused and not self.paged:
            raise ValueError("fused ragged iterations require the paged "
                             "data plane")
        if econf.host_capacity_tokens > 0 and not self.paged:
            raise ValueError("the host-offload KV tier requires the paged "
                             "data plane (dense state is not pageable)")
        if econf.prefetch_budget_tokens > 0 \
                and econf.host_capacity_tokens <= 0:
            raise ValueError("speculative restore prefetches HOST-tier "
                             "spans: set host_capacity_tokens > 0")
        if econf.speculative is not None and not self.fused:
            raise ValueError("speculative decoding rides the fused mixed "
                             "dispatch: it requires the paged fused plane")
        # SPMD submesh (DESIGN.md §13): chips > 1 turns this engine into
        # one tensor-parallel instance. The mesh is built BEFORE the
        # scheduler so token accounting sees the pooled (aggregate)
        # device capacity; single-chip engines take the pre-SPMD path —
        # no mesh, no collectives. A single-chip engine given a device
        # (one replica per chip) commits its params, pool and per-step
        # inputs there; without one it runs on JAX's default device.
        self.chips = max(econf.chips_per_instance, 1)
        self.mesh = None
        self._rep_sharding = None
        self._device_sharding = None
        if self.chips == 1 and devices:
            self._device_sharding = SingleDeviceSharding(devices[0])
            self.params = jax.device_put(self.params, self._device_sharding)
        if self.chips > 1:
            if not self.paged:
                raise ValueError(
                    "tensor-parallel serving (chips_per_instance > 1) "
                    "requires the paged data plane")
            self.mesh = make_serve_mesh(self.chips, devices)
            self._rep_sharding = NamedSharding(self.mesh, P())
        self.scheduler = LocalScheduler(
            LocalSchedulerConfig(
                instance_id=econf.instance_id,
                capacity_tokens=econf.device_capacity_tokens,
                chunk_size=econf.chunk_size,
                max_batch_tokens=econf.max_batch_tokens,
                max_batch_requests=econf.max_batch_requests,
                priority_groups=econf.priority_groups,
                fcfs=econf.fcfs,
                host_capacity_tokens=econf.host_capacity_tokens,
                prefetch_budget_tokens=econf.prefetch_budget_tokens,
                spec_verify_tokens=(econf.speculative.k
                                    if econf.speculative is not None
                                    else 0)),
            on_evict=self._on_evict)
        # External eviction notification — protocol v2 only (DESIGN.md
        # §9): called as cb(instance_id, evicted_spans, demoted=[...],
        # host_dropped=[...]) with content-addressed PrefixSpans and
        # KEYWORD-ONLY tier arguments; GlobalScheduler.on_evictions is
        # wireable directly (its `now` stays at its default).
        self._ext_evict = on_evict
        # per-request live state: next input token (+ cache pytree when dense)
        self.live: Dict[int, Dict[str, Any]] = {}
        # StatsDict (not a plain dict) so the *_overlap_frac ratios are
        # DERIVED at read time instead of recomputed inside the demote/
        # prefetch drain loops; binds to the telemetry registry as
        # engine_* series when a Telemetry is attached.
        self.stats = StatsDict(
            {"reused_tokens": 0, "prefilled_tokens": 0,
             "decode_steps": 0, "iterations": 0,
             "decode_batches": 0, "cache_concat_calls": 0,
             "seed_aliased_pages": 0, "seed_copied_pages": 0,
             "aborted": 0, "model_dispatches": 0,
             "fused_iterations": 0, "fused_padded_tokens": 0,
             "demoted_tokens": 0, "restored_tokens": 0,
             "restore_failures": 0, "demote_dispatches": 0,
             "restore_dispatches": 0, "demote_batches": 0,
             "demote_batches_overlapped": 0,
             "prefetch_issued": 0, "prefetch_hit": 0,
             "prefetch_wasted": 0, "prefetch_dispatches": 0,
             "prefetch_batches": 0,
             "prefetch_batches_overlapped": 0,
             # pure-decode dispatches: lanes dispatched, and those of
             # them that carried padding (mixed steps count theirs in
             # fused_padded_tokens)
             "decode_only_lanes": 0, "decode_only_padded_lanes": 0,
             # speculative decoding (§14): target-side verify outcomes.
             # spec_draft_dispatches counts the DRAFT model's fused
             # propose dispatches — they never touch model_dispatches,
             # which stays the target-dispatch-per-iteration invariant.
             "spec_proposed_tokens": 0, "spec_accepted_tokens": 0,
             "spec_rejected_tokens": 0, "spec_verify_lanes": 0,
             "spec_draft_dispatches": 0, "spec_degraded": 0},
            derived={"demote_overlap_frac":
                     frac_of("demote_batches_overlapped",
                             "demote_batches"),
                     "prefetch_overlap_frac":
                     frac_of("prefetch_batches_overlapped",
                             "prefetch_batches"),
                     "spec_acceptance_frac":
                     frac_of("spec_accepted_tokens",
                             "spec_proposed_tokens")})
        # profiler span names (telemetry.HOST_SPANS) for this instance,
        # built once: the step path formats no strings
        self._span = {name.rsplit(".", 1)[1]:
                      f"{name}.{econf.instance_id}"
                      for name in HOST_SPANS
                      if name.startswith("preble.engine.")}
        self.telemetry = None
        self.failed = False
        # fault injection (DESIGN.md §11): None on fault-free runs —
        # every hook below is behind an `is not None` check, so the
        # normal data plane stays byte-identical
        self.faults = None
        self._cb: Optional[CircuitBreaker] = None
        self.host_store: Optional[HostKVStore] = None
        # draft plane handle (§14): stays None on non-speculative runs
        # AND on the dense plane — every spec hook checks `is not None`
        self.draft: Optional[DraftWorker] = None
        # restores staged by admissions, flushed once per step
        self._pending_restore: List[Tuple[np.ndarray, np.ndarray, Any]] = []
        # speculative restores in flight this step: (record,
        # model_dispatches at issue) — scatter already dispatched,
        # bookkeeping lands at _drain_prefetches after the model runs
        self._prefetch_inflight: List[Tuple[dict, int]] = []
        if self.paged:
            self._init_paged()
        else:
            self._init_dense()

    # ================= paged data plane =====================================

    def _init_paged(self) -> None:
        ps = self.econf.page_size
        # scheduler token accounting keeps usage under the AGGREGATE
        # submesh capacity (capacity_tokens per chip x chips — each chip
        # holds a 1/chips slice of every page, so pooled HBM scales);
        # slack pages absorb page-granularity fragmentation (every live
        # sequence wastes < page_size tokens in its tail page), +1 for
        # the reserved scratch page that padded batch lanes write into.
        # slack scales with concurrency: one partial tail page AND one
        # unaccounted CoW duplicate per live request, + the scratch page
        n_pages = (self.econf.device_capacity_tokens // ps
                   + 2 * self.econf.max_batch_requests + 1)
        self.pool = PagedKVPool(n_pages, ps)
        self._scratch_page = self.pool.reserve_page()   # page 0, pinned
        assert self._scratch_page == 0
        self._pages_per_req = -(-self.econf.max_context // ps)
        specs = self.api.paged_cache_specs(n_pages, ps)
        jit_kw: Dict[str, Any] = {}
        gather_kw: Dict[str, Any] = {}
        if self.mesh is not None:
            # SPMD plane (§13): shard params by serve_policy and the
            # pool leaves by pool_pspec (head-wise when the TP degree
            # divides kv_heads, slot/page-wise GQA fallback otherwise).
            # Out-shardings pin the donated pool's layout so GSPMD can
            # never reshard it across steps (donation stays aliasing).
            policy = shard_lib.serve_policy(self.mesh, self.api.n_bytes)
            self.params = jax.device_put(
                self.params,
                shard_lib.param_shardings(self.api.specs, self.mesh,
                                          policy))
            self._pool_shardings = shard_lib.pool_shardings(specs,
                                                            self.mesh)
            self._span_shardings = shard_lib.span_shardings(specs,
                                                            self.mesh)
            # demote gathers keep every non-page axis shard: drop the
            # page dim's partition, keep slot/head placement per-shard
            self._gathered_shardings = jax.tree.map(
                lambda s: NamedSharding(
                    self.mesh, P(None, *tuple(s.spec)[1:])),
                self._pool_shardings)
            jit_kw = {"out_shardings": (self._rep_sharding,
                                        self._pool_shardings)}
            gather_kw = {"out_shardings": self._gathered_shardings}
            self.pages = shard_lib.placed_zeros(specs, self._pool_shardings)
        elif self._device_sharding is not None:
            self.pages = shard_lib.placed_zeros(specs, self._device_sharding)
        else:
            self.pages = _cache_zeros(specs)
        # params ride as argument 0 of every model dispatch: closed over,
        # they would be baked into each executable as constants.
        # Profiler traces find the step programs by the names
        # _decode_paged_impl and _mixed_paged_impl: keep them.
        self._decode_paged_fn = jax.jit(self._decode_paged_impl,
                                        donate_argnums=(1,), **jit_kw)
        self._extend_paged_fn = jax.jit(self._extend_paged_impl,
                                        donate_argnums=(1,), **jit_kw)
        self._mixed_paged_fn = jax.jit(self._mixed_paged_impl,
                                       donate_argnums=(1,), **jit_kw)
        # speculative decoding (§14): the draft model's own paged plane
        # plus the target's verify variant of the mixed dispatch (same
        # KV writes, + per-position chunk predictions). fail() rebuilds
        # both with the pool, exactly like the target plane.
        if self.econf.speculative is not None:
            self.draft = DraftWorker(self.econf.speculative, self.econf,
                                     mesh=self.mesh,
                                     rep_sharding=self._rep_sharding,
                                     device_sharding=self._device_sharding)
            spec_jit_kw: Dict[str, Any] = {}
            if self.mesh is not None:
                spec_jit_kw = {"out_shardings": (self._rep_sharding,
                                                 self._rep_sharding,
                                                 self._pool_shardings)}
            self._mixed_spec_fn = jax.jit(self._mixed_spec_impl,
                                          donate_argnums=(1,),
                                          **spec_jit_kw)
        else:
            self.draft = None
        self._copy_page_fn = jax.jit(
            self._copy_page_impl, donate_argnums=(0,),
            **({"out_shardings": self._pool_shardings}
               if self.mesh is not None else {}))
        # keep node->page aliases aligned with radix node splits
        self.scheduler.tree.split_hooks.append(self._on_split)
        # hierarchical KV tiering (DESIGN.md §8): the scheduler owns
        # demote/drop policy, PagedHostTier moves the bytes, the store
        # holds them; restores staged at admission are flushed as ONE
        # scatter dispatch per step (batched into the fused iteration).
        self._pending_restore = []
        self._prefetch_inflight = []
        if self.econf.host_capacity_tokens > 0:
            self.host_store = HostKVStore()
            self.scheduler.host_tier = PagedHostTier(self, self.host_store)
            self.scheduler.tree.split_hooks.append(self._on_split_host)
            self._gather_pages_fn = jax.jit(
                lambda pages, idx: jax.tree.map(lambda a: a[idx], pages),
                **gather_kw)
            self._scatter_tokens_fn = jax.jit(
                self._scatter_tokens_impl, donate_argnums=(0,),
                **({"out_shardings": self._pool_shardings}
                   if self.mesh is not None else {}))
        else:
            self.host_store = None

    def _init_dense(self) -> None:
        self.pool = PagedKVPool(
            self.econf.capacity_tokens // self.econf.page_size,
            self.econf.page_size)
        # node path key -> attention-KV slab {p_j: {"k": [G,1,span,KH,D],..}}
        self.kv_store: Dict[PathKey, Pytree] = {}
        # exact-prefix -> recurrent state snapshot (leaf granularity)
        self.state_store: Dict[Tuple[int, ...], Pytree] = {}
        self._cache_spec = self.api.cache_specs(1, self.econf.max_context)
        self._decode_fn = jax.jit(self._decode_impl, donate_argnums=(1,))

    def _decode_impl(self, params, caches, tokens, pos):
        nxt, caches = self.api.decode(params, caches,
                                      {"tokens": tokens, "pos": pos})
        return nxt, caches

    def _decode_paged_impl(self, params, pages, tokens, pos, page_table):
        with shard_lib.mesh_scope(self.mesh):
            return self.api.decode_paged(params, pages,
                                         {"tokens": tokens, "pos": pos,
                                          "page_table": page_table})

    def _extend_paged_impl(self, params, pages, tokens, start, page_table):
        with shard_lib.mesh_scope(self.mesh):
            return self.api.extend_paged(params, pages,
                                         {"tokens": tokens, "start": start,
                                          "page_table": page_table})

    def _mixed_paged_impl(self, params, pages, chunk_tokens, chunk_start,
                          chunk_len, chunk_pt, dec_tokens, dec_pos, dec_pt):
        with shard_lib.mesh_scope(self.mesh):
            return self.api.mixed_paged(params, pages,
                                        {"chunk_tokens": chunk_tokens,
                                         "chunk_start": chunk_start,
                                         "chunk_len": chunk_len,
                                         "chunk_page_table": chunk_pt,
                                         "dec_tokens": dec_tokens,
                                         "dec_pos": dec_pos,
                                         "dec_page_table": dec_pt})

    def _mixed_spec_impl(self, params, pages, chunk_tokens, chunk_start,
                         chunk_len, chunk_pt, dec_tokens, dec_pos, dec_pt):
        # identical batch/KV semantics to _mixed_paged_impl; also
        # returns chunk_pred [Lc, C] — the target's greedy prediction at
        # every chunk position, which is exactly the verification signal
        # for verify lanes carrying [pending, d1..dK]
        with shard_lib.mesh_scope(self.mesh):
            return self.api.mixed_paged_spec(
                params, pages,
                {"chunk_tokens": chunk_tokens, "chunk_start": chunk_start,
                 "chunk_len": chunk_len, "chunk_page_table": chunk_pt,
                 "dec_tokens": dec_tokens, "dec_pos": dec_pos,
                 "dec_page_table": dec_pt})

    def _copy_page_impl(self, pages, src, dst):
        # a page is one [KH, R, 128] block of each pool leaf (per
        # layer; see transformer.paged_cache_specs)
        return jax.tree.map(lambda a: a.at[dst].set(a[src]), pages)

    def _scatter_tokens_impl(self, pages, pids, mask, data):
        """KV scatter of the host-tier restore, a page at a time: write
        data [N, PS, KH, D] into pages ``pids`` [N] at the slots
        ``mask`` [N, PS] sets (attention.write_pages). Padding pages
        are the reserved scratch page 0."""
        return jax.tree.map(lambda a, d: write_pages(a, pids, mask, d),
                            pages, data)

    # ---- host/device batch boundary (DESIGN.md §13) ------------------------

    def _lower_batch(self, wb: ModelWorkerBatch) -> ForwardBatch:
        """ModelWorkerBatch -> ForwardBatch: ONE host->device transfer
        for the step's dense inputs. On a submesh the arrays commit
        replicated; a single-chip engine commits them to its own
        device."""
        with host_span(self._span["lower"]):
            return ForwardBatch.lower(
                wb, self._device_sharding if self.mesh is None
                else self._rep_sharding)

    def _fetch_result(self, *outs) -> List[np.ndarray]:
        """Materialize the dispatch's outputs host-side: the host
        blocks on the device here (on a submesh, on the sharded
        computation and the cross-shard result assembly)."""
        with host_span(self._span["fetch"]):
            return [np.asarray(o) for o in outs]

    def gather_pages_device(self, page_ids: List[int]) -> Tuple[Any, int]:
        """Demote-side snapshot: ONE bucketed device gather over an
        entire eviction plan's pages, into FRESH device buffers — the
        device->host copy is deferred (PagedHostTier.drain) so it
        overlaps the step's model dispatch. Padding indices hit the
        scratch page and are sliced off at drain. Safe against page
        reuse: the gather is dispatched before any later scatter/step
        donates the pool, and the device stream executes in dispatch
        order."""
        n = len(page_ids)
        nb = _bucket(n)
        idx = np.zeros(nb, np.int32)
        idx[:n] = page_ids
        gathered = self._gather_pages_fn(self.pages, jnp.asarray(idx))
        self.stats["demote_dispatches"] += 1
        return gathered, n

    def _drain_demotes(self) -> None:
        """Land pending demote bytes host-side (end of step, or forced
        by a read that needs the store complete)."""
        ht = self.scheduler.host_tier
        if ht is not None:
            ht.drain()

    def _host_entry(self, key):
        """Store lookup that only forces the pending demote DMA to land
        when THIS entry is still in flight — ordinary misses (never-
        demoted nodes) must not break the demote/compute overlap."""
        e = self.host_store.get(key)
        if e is None:
            ht = self.scheduler.host_tier
            if ht is not None and ht.pending_has(key):
                ht.drain()
                e = self.host_store.get(key)
        return e

    # ---- host-side page bookkeeping ----------------------------------------

    def _page_table_rows(self, seq_ids, n_rows: Optional[int] = None
                         ) -> np.ndarray:
        """[n_rows, P] int32 page ids; rows beyond a sequence's pages —
        and whole padding rows — point at the reserved scratch page 0
        (masked by lens on the read side; padded lanes write into it)."""
        n_rows = n_rows if n_rows is not None else len(seq_ids)
        pt = np.zeros((n_rows, self._pages_per_req), np.int32)
        for i, sid in enumerate(seq_ids):
            pages = self.pool.tables[sid].pages
            pt[i, :len(pages)] = pages
        return pt

    def _append_with_cow(self, seq_id, tokens: int) -> None:
        """pool.append + the device-side half of copy-on-write: when
        append replaces a shared partial tail page with a private one
        (observed as the page id at the old tail index changing), the
        old page's contents are copied page-granularly on device — the
        only copy in the reuse path; it never happens for page-aligned
        reuse boundaries."""
        t = self.pool.tables[seq_id]
        old_tail = t.pages[-1] if t.pages else None
        tail_idx = len(t.pages) - 1
        self.pool.append(seq_id, tokens)
        if old_tail is not None and t.pages[tail_idx] != old_tail:
            self.pages = self._copy_page_fn(
                self.pages, jnp.int32(old_tail),
                jnp.int32(t.pages[tail_idx]))
            self.stats["seed_copied_pages"] += 1

    def _ensure_free(self, tokens: int, now: float = 0.0) -> None:
        """The scheduler's token accounting keeps the pool under
        capacity, but page-granularity fragmentation can briefly exceed
        it: reclaim LRU cached nodes (through the scheduler's own
        accounting) until the pool fits the reservation."""
        sch, inst = self.scheduler, self.econf.instance_id
        while self.pool.free_tokens() < tokens:
            # pages shared between nodes free fewer pool tokens than the
            # plan's token count, so loop until the pool actually fits
            plan = sch.tree.plan_eviction(
                inst, tokens - self.pool.free_tokens())
            if not plan:
                raise MemoryError(
                    f"KV pool exhausted: need {tokens} tokens, "
                    f"free {self.pool.free_tokens()}, nothing evictable")
            sch.apply_eviction(plan, now)

    def _on_split(self, head, tail) -> None:
        """RadixTree split hook, path-keyed: the TAIL keeps the
        pre-split key (its end boundary is unchanged), so the existing
        ``("node", key)`` table — which covers the deeper alias —
        already sits under the tail's key; the head gets a prefix fork
        at its new boundary. Pure refcount moves, no device traffic."""
        key_t = ("node", tail.path_key)        # the pre-split key
        t = self.pool.tables.get(key_t)
        if t is None:
            return
        d_head = head.depth_tokens()
        d_tail = d_head + len(tail.tokens)
        key_h = ("node", head.path_key)
        if key_h in self.pool.tables:          # digest collision guard
            return
        if t.num_tokens >= d_tail:
            # table serves the tail fully; head aliases its prefix
            self.pool.fork(key_t, key_h, d_head)
        else:
            # coverage ends inside the head's span: the alias belongs
            # to the head alone (same outcome as the pre-§9 head-keyed
            # trim — tokens between d_head and coverage are dropped)
            self.pool.fork(key_t, key_h, min(d_head, t.num_tokens))
            self.pool.release(key_t)

    def _on_split_host(self, head, tail) -> None:
        """Split hook for the host tier: a demoted span crossing the
        new node boundary is split between head and tail entries. If
        that span's demote DMA is still in flight, land it first —
        otherwise the store would miss the split the scheduler's LRU
        already applied and the two tiers diverge permanently (the
        deferred drain would file the full span under the tail key)."""
        if self.host_store is not None:
            ht = self.scheduler.host_tier
            # at hook time tail.path_key IS the pre-split key
            if ht is not None and ht.pending_has(tail.path_key):
                ht.drain()
            self.host_store.on_split(head, tail)

    # ---- eviction hook ------------------------------------------------------

    def _on_evict(self, instance_id: int, spans: List[PrefixSpan], *,
                  demoted: List[PrefixSpan] = (),
                  host_dropped: List[PrefixSpan] = ()) -> None:
        if self.paged:
            # offload engines: demote_many already released the tables
            # of spans it SAW, but the scheduler's admission policy may
            # skip spans entirely (one-shot under host pressure,
            # ambiguous keys) — release unconditionally; releasing an
            # already-released table is a no-op, a leaked one would pin
            # its pages forever (scheduler accounting no longer counts
            # them, so plan_eviction could never reclaim them)
            for s in spans:
                self.pool.release(("node", s.key))
        else:
            for s in spans:
                self.kv_store.pop(s.key, None)
        if self._ext_evict is not None:
            self._ext_evict(instance_id, spans, demoted=list(demoted),
                            host_dropped=list(host_dropped))

    # ---- admission ----------------------------------------------------------

    def _admit(self, r: Request, now: float) -> None:
        total = r.prompt_len + r.max_new_tokens
        if total > self.econf.max_context:
            # reject before any pool/cache state exists: both planes
            # would otherwise corrupt silently (dense clamps its cache
            # writes; paged overflows its page-table row)
            raise AdmissionError(
                f"request {r.request_id}: prompt+max_new = {total} "
                f"exceeds max_context {self.econf.max_context}")
        if self.paged:
            self._admit_paged(r, now)
        else:
            self._admit_dense(r, now)

    def _admit_paged(self, r: Request, now: float) -> None:
        """Seed a request by ALIASING the matched prefix's pages: fork
        the deepest covering node sequence — refcount increments only,
        zero KV device copies (DESIGN.md §4). With the host tier, the
        reusable prefix may extend past the aliased part through
        demoted spans: those are RESTORED — fresh pages are allocated
        and the host KV is staged for one batched scatter in this
        step's fused dispatch — instead of recomputed."""
        # the match is always node-aligned here: _reserve already ran
        # tree.insert(r.tokens), which split any partially-matching
        # node at this prompt's boundary (splits are the only boundary
        # edits; nodes never merge), so no mid-node case exists
        m = self.scheduler.tree.match(r.tokens, now=now)
        best_key, best_len, off = None, 0, 0
        for node in m.path:
            off += len(node.tokens)
            t = self.pool.tables.get(("node", node.path_key))
            if t is not None and t.num_tokens >= off:
                best_key, best_len = ("node", node.path_key), off
        # a fully-cached prompt must still run its LAST token through
        # the model — that forward produces the first output token
        # (same rule as vLLM/SGLang: reuse cap = prompt_len - 1)
        reuse = min(best_len, r.prompt_len - 1)
        # host-tier restore plan: demoted spans contiguously extending
        # the aliased prefix (planned BEFORE _ensure_free, revalidated
        # after — freeing room can cascade into host-capacity drops)
        restore_plan: List[Tuple[PathKey, int, int, int]] = []
        # an OPEN circuit breaker (repeated restore-DMA failures)
        # disables restore planning for its cooldown: the request
        # recomputes the demoted span instead of thrashing a bad path
        if self.host_store is not None and best_len == reuse \
                and (self._cb is None or self._cb.allow(now)):
            restore_plan, _ = self._host_restore_chain(
                m, reuse, r.prompt_len - 1)
        rid = ("req", r.request_id)
        need = r.prompt_len - reuse + r.max_new_tokens
        # + one page of headroom for the CoW of a shared partial tail
        self._ensure_free(need + self.pool.page_size, now)
        restore_end = reuse
        for key, nid, lo, hi in restore_plan:
            if self.faults is not None and self.faults.dma_fails("restore"):
                # injected host->device DMA failure: degrade to
                # recomputing the rest of the chain; the breaker opens
                # the whole restore path after repeated hits
                self.stats["restore_failures"] += 1
                if self._cb is not None:
                    self._cb.record_failure(now)
                break
            e = self._host_entry(key)
            if (e is None or e.node_id != nid
                    or e.start > lo or e.start + e.length < hi):
                # host entry evicted mid-flight (demote cascade of
                # _ensure_free overflowed the host budget) or rekeyed
                # under a collided digest: fall back to recomputing
                # the rest of the chain
                self.stats["restore_failures"] += 1
                break
            restore_end = hi
        if self._cb is not None and restore_end > reuse:
            self._cb.record_success()
        restore_plan = [(key, nid, lo, min(hi, restore_end))
                        for key, nid, lo, hi in restore_plan
                        if lo < restore_end]
        if best_key is not None and reuse > 0:
            self.pool.fork(best_key, rid, reuse)
            self.stats["seed_aliased_pages"] += len(
                self.pool.tables[rid].pages)
        else:
            reuse = 0
            self.pool.create(rid)
        try:
            self._append_with_cow(rid, need)
        except MemoryError:
            self.pool.release(rid)    # don't leak the table: a retry
            raise                     # would trip pool.create's assert
        if restore_end > reuse:
            self._stage_restore(r, rid, reuse, restore_end, restore_plan)
        # the scheduler reserved prompt - device_cached_len + max_new,
        # but the engine may alias a different prefix length (matched
        # nodes whose pages were never stored / already evicted / more
        # coverage than the plan assumed); surface the difference so
        # admission gating sees the pool's true occupancy. Restored
        # tokens occupy fresh pages, so only the ALIASED length offsets
        # the reservation.
        delta = r.device_cached_len - reuse
        if delta:
            self.scheduler.used_tokens = max(
                self.scheduler.used_tokens + delta, 0)
        # everything beyond the aliased prefix is this request's private
        # pool usage until _store_prefix publishes spans to node aliases
        # (credit_stored); the unpublished rest is refunded at release
        self.scheduler.set_account(r.request_id, need)
        self.live[r.request_id] = {"next": None}
        r.prefill_done = restore_end
        self.stats["reused_tokens"] += restore_end

    def _host_restore_chain(self, m, boundary: int, limit: int
                            ) -> Tuple[List[Tuple[PathKey, int, int, int]],
                                       int]:
        """Walk the match path past the device-aliased ``boundary`` and
        chain host entries that contiguously extend it, stopping at the
        first hole or ``limit`` (= prompt_len - 1, the reuse cap).
        Entries resolve by path key with node-ownership verification
        (collision guard); an entry whose demote DMA is still in flight
        forces a targeted drain. Returns ([(key, node_id, lo, hi)],
        new_boundary) in absolute token depths."""
        plan: List[Tuple[PathKey, int, int, int]] = []
        cum = 0
        for node in m.path:
            node_start = cum
            cum += len(node.tokens)
            if cum <= boundary:
                continue
            if node_start != boundary or boundary >= limit:
                break
            e = self._host_entry(node.path_key)
            if e is None or e.node_id != node.node_id \
                    or e.start != node_start:
                break
            take = min(e.length, limit - boundary)
            if take <= 0:
                break
            plan.append((node.path_key, node.node_id, node_start,
                         node_start + take))
            boundary = node_start + take
            if boundary < cum:        # partial span ends the chain
                break
        return plan, boundary

    def _stage_restore(self, r: Request, rid, lo: int, hi: int,
                       plan: List[Tuple[PathKey, int, int, int]]) -> None:
        """Stage the host->device scatter for tokens [lo, hi) of the
        request's sequence: map each restored token onto its (page,
        slot) in the request's freshly appended table and queue the
        host KV; ``_flush_restores`` runs ONE scatter dispatch per step
        for all admissions (batched into the fused iteration)."""
        pidx, sidx = self._token_page_slots(self.pool.tables[rid],
                                            self.pool.page_size, lo, hi)
        chunks = [self.host_store.read_span(key, nid, a, b)
                  for key, nid, a, b in plan]
        data = (chunks[0] if len(chunks) == 1
                else jax.tree.map(lambda *xs: np.concatenate(xs, 0),
                                  *chunks))
        self._pending_restore.append((pidx, sidx, data))
        for key, _, _, _ in plan:
            self.scheduler.touch_host(key)
        self.stats["restored_tokens"] += hi - lo

    def _scatter_staged(self, staged: List[Tuple]) -> None:
        """ONE donated, bucketed page scatter for a list of staged
        (pidx, sidx, data) triples — shared by the admission restore
        flush and the speculative-restore stream so padding (zero page
        ids target the reserved scratch page) and bucketing can never
        diverge between the two DMA paths. The tokens are laid out
        host-side into the pages they land on, with a mask of the slots
        they fill, so the device writes whole pages."""
        pidx = np.concatenate([s[0] for s in staged])
        sidx = np.concatenate([s[1] for s in staged])
        pids, slot_page = np.unique(pidx, return_inverse=True)
        n, ps = len(pids), self.pool.page_size
        nb = _bucket(n)
        pp = np.zeros(nb, np.int32)
        pp[:n] = pids
        mask = np.zeros((nb, ps), bool)
        mask[slot_page, sidx] = True

        def lay_out(*leaves):
            x = (leaves[0] if len(leaves) == 1
                 else np.concatenate(leaves, axis=0))
            out = np.zeros((nb, ps) + x.shape[1:], x.dtype)
            out[slot_page, sidx] = x
            return out

        data = jax.tree.map(lay_out, *[s[2] for s in staged])
        if self.mesh is not None:
            # per-shard DMA: each chip receives exactly its own slice
            # of the restored KV (head shard when the pool is
            # head-sharded; replicated payload otherwise, with the
            # scatter's index arithmetic routing rows to the owning
            # shard)
            dev = jax.device_put(
                (pp, mask), (self._rep_sharding, self._rep_sharding))
            data = jax.device_put(data, self._span_shardings)
            self.pages = self._scatter_tokens_fn(
                self.pages, dev[0], dev[1], data)
            return
        self.pages = self._scatter_tokens_fn(
            self.pages, jnp.asarray(pp), jnp.asarray(mask),
            jax.tree.map(jnp.asarray, data))

    @staticmethod
    def _token_page_slots(table, page_size: int, lo: int, hi: int
                          ) -> Tuple[np.ndarray, np.ndarray]:
        """(page, slot) coordinates of tokens [lo, hi) in a table."""
        toks = np.arange(lo, hi)
        pages_arr = np.asarray(table.pages, np.int32)
        return pages_arr[toks // page_size], (toks % page_size).astype(
            np.int32)

    def _flush_restores(self) -> None:
        """Apply every restore staged by this step's admissions as ONE
        donated, bucketed scatter dispatch; padding lanes target the
        reserved scratch page."""
        staged, self._pending_restore = self._pending_restore, []
        if not staged:
            return
        self._scatter_staged(staged)
        self.stats["restore_dispatches"] += 1

    # ---- speculative restore: the second DMA stream (DESIGN.md §10) ---------

    def _issue_prefetches(self, now: float) -> None:
        """Ask the scheduler's prefetch queue for work, stage each
        record's host bytes onto fresh pages, and issue ONE batched
        (page, slot) scatter for all of them — dispatched BEFORE the
        step's fused model dispatch, so the DMA rides ahead of compute
        on the device stream exactly like the admission-restore flush;
        the bookkeeping drains after the model runs (overlap). Runs
        after this step's admissions, so no record is ever in flight
        while ``_admit_paged`` walks the tables."""
        if self.host_store is None or not self.scheduler.prefetch_enabled:
            return
        if self._cb is not None and not self._cb.allow(now):
            return              # breaker open: no speculative DMA either
        staged: List[Tuple[dict, Tuple]] = []
        for rec in self.scheduler.plan_prefetch(now):
            if self.faults is not None and self.faults.dma_fails("prefetch"):
                # injected speculative-restore DMA failure: cancel the
                # record (reservation refunds, admission will restore
                # or recompute on the critical path instead)
                if self._cb is not None:
                    self._cb.record_failure(now)
                self.scheduler.cancel_prefetch(rec["id"], now)
                continue
            got = self._stage_prefetch(rec)
            if got is None:
                self.scheduler.cancel_prefetch(rec["id"], now)
            else:
                if self._cb is not None:
                    self._cb.record_success()
                staged.append((rec, got))
        if not staged:
            return
        self._scatter_staged([s for _, s in staged])
        self.stats["prefetch_dispatches"] += 1
        self._prefetch_inflight = [
            (rec, self.stats["model_dispatches"]) for rec, _ in staged]

    def _stage_prefetch(self, rec: dict) -> Optional[Tuple]:
        """Build one record's device-side staging: fork the deepest
        node table covering the record's device boundary into a
        ``("pf", id)`` table, append fresh pages for [lo, hi), and map
        every prefetched token onto its (page, slot). Revalidates the
        host entries against the byte store (an entry mid-demote forces
        a targeted drain, exactly like admission restore) and trims the
        record to what actually exists. Returns (pidx, sidx, data) or
        None when the chain cannot be staged."""
        sch = self.scheduler
        tokens, lo = rec["tokens"], rec["lo"]
        m = sch.tree.match(tokens)
        best_key, best_len, off = None, 0, 0
        for node in m.path:
            off += len(node.tokens)
            if off > lo:
                break
            t = self.pool.tables.get(("node", node.path_key))
            if t is not None and t.num_tokens >= off:
                best_key, best_len = ("node", node.path_key), off
        if lo > 0 and best_len < lo:
            return None     # device base never materialized: the
                            # landed span could not be reached anyway
        hi_eff = lo
        chunks = []
        for key, nid, a, b in rec["spans"]:
            self._host_entry(key)   # land an in-flight demote first
            piece = self.host_store.read_span(key, nid, a, b,
                                              speculative=True)
            if piece is None:
                break
            chunks.append(piece)
            hi_eff = b
        if hi_eff <= lo:
            return None
        if hi_eff < rec["hi"]:
            sch.trim_prefetch(rec["id"], hi_eff)
            if rec["cancelled"]:
                return None
        pfid = ("pf", rec["id"])
        if best_key is not None and lo > 0:
            self.pool.fork(best_key, pfid, lo)
        else:
            self.pool.create(pfid)
        try:
            self._append_with_cow(pfid, hi_eff - lo)
        except MemoryError:
            self.pool.release(pfid)
            return None     # fragmentation squeeze: never evict for
                            # speculative work at staging time
        pidx, sidx = self._token_page_slots(self.pool.tables[pfid],
                                            self.pool.page_size, lo, hi_eff)
        data = (chunks[0] if len(chunks) == 1
                else jax.tree.map(lambda *xs: np.concatenate(xs, 0),
                                  *chunks))
        rec["pfid"] = pfid
        self.stats["prefetch_issued"] += hi_eff - lo
        return pidx, sidx, data

    def _drain_prefetches(self, now: float) -> None:
        """Land this step's speculative restores: publish each record's
        pages as node aliases (zero-copy forks at the issue-time
        boundaries — cancel-on-split guarantees they still hold), hand
        the policy bookkeeping back to the scheduler, and record
        whether the DMA actually overlapped a model dispatch."""
        inflight, self._prefetch_inflight = self._prefetch_inflight, []
        for rec, disp_at in inflight:
            pfid = rec.get("pfid")
            if rec["cancelled"]:
                # cancelled mid-flight (split under it, abort): the
                # scatter already ran — release the staging pages, the
                # bytes are wasted
                if pfid is not None and pfid in self.pool.tables:
                    self.pool.release(pfid)
                continue
            for key, _, _, b in rec["spans"]:
                nkey = ("node", key)
                if nkey not in self.pool.tables:
                    self.pool.fork(pfid, nkey, b)
            self.pool.release(pfid)
            self.scheduler.complete_prefetch(rec["id"], now)
            self.stats["prefetch_batches"] += 1
            if self.stats["model_dispatches"] > disp_at:
                self.stats["prefetch_batches_overlapped"] += 1
        # prefetch_overlap_frac is a derived StatsDict key — computed
        # at read time, never recomputed here in the drain loop

    def _admit_dense(self, r: Request, now: float) -> None:
        cache = _cache_zeros(self._cache_spec)
        m = self.scheduler.tree.match(r.tokens, now=now)
        reuse = 0
        if m.matched_len and not self.has_recurrent:
            reuse = self._seed_attn_kv(cache, m)
        elif m.matched_len and self.has_recurrent:
            reuse = self._seed_snapshot(cache, r.tokens, m.matched_len)
        reuse = min(reuse, r.prompt_len - 1)
        if self.pool.free_tokens() >= (r.prompt_len - reuse
                                       + r.max_new_tokens):
            self.pool.create(r.request_id)
            self.pool.append(r.request_id,
                             r.prompt_len - reuse + r.max_new_tokens)
        # attention stacks publish per-node slabs in _store_prefix
        # (credit_stored); recurrent stacks publish nothing per node —
        # their inserted tree nodes stay marked and are refunded by
        # eviction, so only the outputs die with the request (refunding
        # the prompt part too would double-count with that eviction)
        self.scheduler.set_account(
            r.request_id,
            r.max_new_tokens if self.has_recurrent
            else max(r.prompt_len - r.device_cached_len, 0)
            + r.max_new_tokens)
        self.live[r.request_id] = {"cache": cache, "next": None}
        r.prefill_done = reuse
        self.stats["reused_tokens"] += reuse

    def _seed_attn_kv(self, cache: Pytree, m) -> int:
        """DENSE reference: copy cached KV slabs of the matched path
        into cache[:reuse] (the copies the paged plane exists to avoid)."""
        off = 0
        for node in m.path:
            slab = self.kv_store.get(node.path_key)
            if slab is None:
                break
            span = len(node.tokens)
            for pj, c in slab.items():
                for name in ("k", "v"):
                    cache[pj][name] = jax.lax.dynamic_update_slice(
                        cache[pj][name], c[name],
                        (0, 0, off, 0, 0))
            off += span
        # partial tail inside the next node
        if off < m.matched_len and m.last_node is not None \
                and m.last_node_matched < len(m.last_node.tokens):
            slab = self.kv_store.get(m.last_node.path_key)
            if slab is not None:
                take = m.last_node_matched
                for pj, c in slab.items():
                    for name in ("k", "v"):
                        part = jax.lax.dynamic_slice(
                            c[name], (0, 0, 0, 0, 0),
                            (c[name].shape[0], 1, take,
                             c[name].shape[3], c[name].shape[4]))
                        cache[pj][name] = jax.lax.dynamic_update_slice(
                            cache[pj][name], part, (0, 0, off, 0, 0))
                off += take
        return off

    def _seed_snapshot(self, cache: Pytree, tokens, matched_len: int) -> int:
        """Recurrent/hybrid archs: reuse the longest stored snapshot
        whose key is a prefix of this prompt. A snapshot is a FULL cache
        image at its boundary L: recurrent states after L tokens plus
        the first L positions of every attention-KV buffer."""
        best_len, best = 0, None
        for key, snap in self.state_store.items():
            L = len(key)
            if best_len < L <= matched_len and tuple(tokens[:L]) == key:
                best_len, best = L, snap
        if best is None:
            return 0
        for pj in cache:
            for name, arr in best[pj].items():
                if arr.shape == cache[pj][name].shape:
                    cache[pj][name] = arr
                else:   # k/v slab [G, 1, L, KH, D] -> write at [0:L]
                    cache[pj][name] = jax.lax.dynamic_update_slice(
                        cache[pj][name], arr, (0,) * arr.ndim)
        return best_len

    def _snapshot_full_cache(self, r: Request, boundary: int) -> None:
        """Copy the request's cache at ``boundary`` consumed tokens
        (called mid-prefill at prompt_len - 1, so a future identical
        prompt can reuse everything but its final token). Copies are
        mandatory: live buffers are later donated to the decode jit."""
        key = tuple(r.tokens[:boundary])
        if key in self.state_store:
            return
        cache = self.live[r.request_id]["cache"]
        snap = {}
        for pj, c in cache.items():
            snap[pj] = {}
            for name, arr in c.items():
                if name in ("k", "v") and arr.ndim == 5:
                    arr = arr[:, :, :boundary]
                snap[pj][name] = jnp.array(arr, copy=True)
        self.state_store[key] = snap

    # ---- post-prefill: publish the prompt's KV to the prefix store ----------

    def _store_prefix(self, r: Request, now: float) -> None:
        # re-insert of the path _reserve already counted: mark + publish
        # without recording a second window-H hit for the same serve
        # (the hit rate feeds E2's n_j AND the host-tier admission
        # weighting — double-counting would make every one-shot 'hot')
        path = self.scheduler.tree.insert(
            r.tokens, instance=self.econf.instance_id, now=now,
            record=False)
        if self.paged:
            # alias the request's pages per radix node: each node's
            # sequence covers the full root->node token path, so any
            # later match can fork from the deepest covering node.
            # Publishing a span moves its tokens from the request's
            # private account to the prefix store (eviction refunds
            # them later; release no longer does).
            rid = ("req", r.request_id)
            if rid not in self.pool.tables:
                return
            off = 0
            for node in path:
                off += len(node.tokens)
                key = ("node", node.path_key)
                if key not in self.pool.tables:
                    self.pool.fork(rid, key, off)
                    self.scheduler.credit_stored(r.request_id,
                                                 len(node.tokens))
            return
        if not self.has_recurrent:
            cache = self.live[r.request_id]["cache"]
            off = 0
            for node in path:
                span = len(node.tokens)
                if node.path_key not in self.kv_store:
                    slab = {}
                    for pj, c in cache.items():
                        slab[pj] = {
                            name: jax.lax.dynamic_slice(
                                c[name], (0, 0, off, 0, 0),
                                (c[name].shape[0], 1, span,
                                 c[name].shape[3], c[name].shape[4]))
                            for name in ("k", "v") if name in c}
                    self.kv_store[node.path_key] = slab
                    self.scheduler.credit_stored(r.request_id, span)
                off += span
        # (recurrent archs snapshot mid-prefill at prompt_len - 1 —
        # see _snapshot_full_cache; nothing to store here)

    # ---- the iteration -------------------------------------------------------

    def step(self, now: float) -> List[Request]:
        """Run one continuous-batching iteration; returns finished reqs.

        Paged fused plane (default): admission is host-side page
        bookkeeping, then ALL prefill chunks and decode slots run as ONE
        donated ragged dispatch (`_run_mixed`) — dispatches/iteration
        are O(1) in the number of active prefills. Pure-decode
        iterations keep the PR-1 slot decode step (same O(1),
        cheaper gather). The unfused paged and dense planes serialize
        per-request prefills before the decode batch (reference
        behavior)."""
        with host_span(self._span["step"]):
            return self._step(now)

    def _step(self, now: float) -> List[Request]:
        with host_span(self._span["form_batch"]):
            batch = self.scheduler.form_batch(now)
        if not batch.items and not self.scheduler.prefetch_enabled:
            if self.faults is not None \
                    and self.faults.take_crash(self.econf.instance_id):
                raise InstanceCrashed(self.econf.instance_id)
            return []
        finished: List[Request] = []
        aborted: List[Request] = []
        newly_prefilled: List[Request] = []
        if batch.items:
            self.stats["iterations"] += 1
            with host_span(self._span["admit"]):
                aborted = self._admit_new(batch, now)
                if aborted:
                    batch.items = [it for it in batch.items
                                   if it.request not in aborted]
                # host-tier restores staged by this step's admissions land
                # as one batched scatter BEFORE the model reads any lane KV
                if self._pending_restore:
                    self._flush_restores()

        # speculative restores issue AFTER admission (no record is in
        # flight while _admit_paged walks tables) and BEFORE the model
        # dispatch: the scatter rides ahead of compute on the device
        # stream, and the host-side bookkeeping drains after it
        self._issue_prefetches(now)

        # armed mid-step crash fires HERE — after admissions took pool
        # pages and prefetch scatters went in flight, before the model
        # runs: the worst spot, with DMA and reservations stranded
        if self.faults is not None \
                and self.faults.take_crash(self.econf.instance_id):
            raise InstanceCrashed(self.econf.instance_id)

        if batch.items:
            has_prefill = any(it.chunk_tokens > 0
                              for it in batch.prefill_items())
            # speculative engines route decode-only iterations through
            # _run_mixed too: their decode slots become verify chunks,
            # still ONE target dispatch per iteration either way
            if self.fused and (has_prefill or self.draft is not None):
                newly_prefilled = self._run_mixed(batch)
            else:
                # -- prefill items (each runs alone: variable chunk/position)
                newly_prefilled = self._run_prefills(batch)
                # -- decode items (one batched step) --
                dec = [it.request for it in batch.decode_items()]
                if dec and self.paged:
                    self._decode_batch_paged(dec)
                elif dec:
                    self._decode_batch_dense(dec)

            # -- advance scheduler state --
            with host_span(self._span["finish"]):
                finished = self.scheduler.complete_iteration(batch, now)
                for r in newly_prefilled:
                    self._store_prefix(r, now)
                for item in batch.items:
                    r = item.request
                    if item.phase == "decode" and r.output_tokens:
                        r.output_tokens[-1] = \
                            self.live[r.request_id]["next"]
                for r in finished:
                    lv = self.live.pop(r.request_id, None)
                    self.pool.release(("req", r.request_id)
                                      if self.paged else r.request_id)
                    if self.draft is not None:
                        self.draft.release(r.request_id)
                        self._observe_spec(r, lv, now)
        # land this step's speculative restores (the publish runs after
        # _store_prefix so a same-step split cancels cleanly first),
        # then any demote DMA — both gathers/scatters were dispatched
        # before the model work above, so the copies rode behind
        # compute (the *_overlap_frac stats measure how often)
        self._drain_prefetches(now)
        if self.host_store is not None:
            self._drain_demotes()
            self.stats["prefetch_hit"] = self.scheduler.stats[
                "prefetch_hit"]
            self.stats["prefetch_wasted"] = self.scheduler.stats[
                "prefetch_wasted"]
        # aborted requests are terminal too (state FAILED) — surface
        # them so cluster runtimes can account/resubmit
        return finished + aborted

    def _admit_new(self, batch: Batch, now: float) -> List[Request]:
        """Admit this iteration's not-yet-live prefill requests (pure
        host-side page bookkeeping on the paged plane) and re-clamp
        every chunk through the scheduler's single clamp helper — the
        engine may reuse a different prefix length than the plan
        assumed. Unservable requests (oversized prompt / pool
        exhausted) abort without killing the instance."""
        aborted: List[Request] = []
        for item in batch.prefill_items():
            r = item.request
            if r.request_id not in self.live:
                try:
                    self._admit(r, now)
                except (AdmissionError, MemoryError):
                    self.scheduler.abort(r)
                    self.stats["aborted"] += 1
                    aborted.append(r)
                    continue
            self.scheduler.clamp_chunk(
                item, snapshot_boundary=self.has_recurrent)
        return aborted

    # ---- dispatch shapes ---------------------------------------------------

    # Every paged dispatch pads to the configured maxima, whatever the
    # step carries: max_batch_requests lanes in each half, chunk_size
    # tokens per chunk lane (K+1 if speculative verify lanes are
    # longer). The engine so runs exactly one mixed and one decode
    # program, which ``warmup`` compiles before traffic — on a chip one
    # full-depth step takes a minute to compile.

    def _lanes(self) -> int:
        return self.econf.max_batch_requests

    def _chunk_len(self) -> int:
        k = self.draft.k if self.draft is not None else 0
        return max(self.econf.chunk_size, k + 1)

    def _padding_batch(self) -> ForwardBatch:
        """A batch of padding lanes only (every page-table row is the
        reserved scratch page), lowered like a step's."""
        n, c = self._lanes(), self._chunk_len()
        p = self._pages_per_req
        return self._lower_batch(ModelWorkerBatch(
            np.zeros((n, c), np.int32), np.zeros(n, np.int32),
            np.zeros(n, np.int32), np.zeros((n, p), np.int32),
            np.zeros(n, np.int32), np.zeros(n, np.int32),
            np.zeros((n, p), np.int32)))

    def _lowered(self, fb: ForwardBatch):
        """The mixed and decode dispatches and the CoW page copy,
        lowered."""
        return [self._mixed_paged_fn.lower(
                    self.params, self.pages, fb.chunk_tokens, fb.chunk_start,
                    fb.chunk_len, fb.chunk_page_table, fb.dec_tokens,
                    fb.dec_pos, fb.dec_page_table),
                self._decode_paged_fn.lower(
                    self.params, self.pages, fb.dec_tokens, fb.dec_pos,
                    fb.dec_page_table),
                self._copy_page_fn.lower(self.pages, jnp.int32(0),
                                         jnp.int32(0))]

    def warmup(self) -> None:
        """Compile the mixed and decode programs (and the page copy)
        before traffic, all at once (XLA compiles outside the
        interpreter lock). The steps' dispatches then find them
        compiled. A no-op off the fused paged plane."""
        if not self.fused:
            return
        lowered = self._lowered(self._padding_batch())
        with ThreadPoolExecutor(max_workers=len(lowered)) as pool:
            for fut in [pool.submit(lo.compile) for lo in lowered]:
                fut.result()

    def mixed_dispatch_text(self) -> str:
        """Compiled text of the mixed dispatch: shows which
        kernels and collectives the step program holds (after
        ``warmup`` this reuses the compiled program)."""
        return self._lowered(self._padding_batch())[0].compile().as_text()

    def _run_prefills(self, batch: Batch) -> List[Request]:
        """Serial per-request prefill chunks (dense plane and the
        unfused paged baseline): one dispatch per chunk."""
        newly_prefilled: List[Request] = []
        for item in batch.prefill_items():
            r = item.request
            start, chunk = r.prefill_done, item.chunk_tokens
            if chunk <= 0:
                continue
            toks = jnp.asarray(r.tokens[start:start + chunk], jnp.int32)
            if self.paged:
                pt = jnp.asarray(
                    self._page_table_rows([("req", r.request_id)]))
                nxt, self.pages = self._extend_paged_fn(
                    self.params, self.pages, toks[None], jnp.int32(start),
                    pt)
            else:
                cache = self.live[r.request_id]["cache"]
                nxt, cache = self.api.extend(
                    self.params, cache, {"tokens": toks[None],
                                         "start": jnp.int32(start)})
                self.live[r.request_id]["cache"] = cache
            self.stats["prefilled_tokens"] += chunk
            self.stats["model_dispatches"] += 1
            if self.has_recurrent and start + chunk == r.prompt_len - 1:
                self._snapshot_full_cache(r, r.prompt_len - 1)
            if start + chunk >= r.prompt_len:
                # prefill emits the FIRST generated token
                tok = int(nxt[0])
                self.live[r.request_id]["next"] = tok
                r.output_tokens.append(tok)
                newly_prefilled.append(r)
        return newly_prefilled

    def _run_mixed(self, batch: Batch) -> List[Request]:
        """Fused ragged iteration (DESIGN.md §7): pack every prefill
        chunk and decode slot into ONE donated dispatch. Chunks fill a
        [Lc, C] half padded to the static chunk length (each
        lane addressed by its page-table row and start position);
        decode slots fill a [Ld] single-token half — so each lane's KV
        is gathered once, and per-iteration model dispatches are O(1)
        in the number of active prefills. Padding lanes carry all-zero
        table rows (the reserved scratch page absorbs their KV writes);
        padded chunk tokens are redirected to scratch inside the
        kernel. (Lc, C, Ld) are the static maxima, so every step runs
        the one program ``warmup`` compiled."""
        chunk_items = [it for it in batch.prefill_items()
                       if it.chunk_tokens > 0]
        dec_items = batch.decode_items()
        if not chunk_items and not dec_items:
            return []
        # --- speculative split (§14): decode slots with >= 2 tokens of
        # output headroom become K+1-token verify chunks; the rest (and
        # any lane the draft pool couldn't stage) stay plain decode ---
        spec_lanes: List[Tuple[Request, int, List[int], int]] = []
        plain_dec = dec_items
        if self.draft is not None and dec_items:
            want: List[Tuple[Request, int]] = []
            plain_dec = []
            for it in dec_items:
                r = it.request
                # committing a + 1 <= k_eff + 1 tokens this step must
                # never overshoot max_new_tokens (output_tokens already
                # holds the pending token)
                k_eff = min(self.draft.k,
                            r.max_new_tokens - len(r.output_tokens) - 1)
                if k_eff > 0:
                    want.append((r, k_eff))
                else:
                    plain_dec.append(it)
            props = self.draft.propose(want) if want else {}
            for r, k_eff in want:
                d = props.get(r.request_id)
                if d is None:       # draft pool squeeze: degrade
                    plain_dec.append(next(
                        it for it in dec_items if it.request is r))
                else:
                    pos = r.prompt_len + len(r.output_tokens) - 1
                    spec_lanes.append((r, k_eff, d, pos))
            self.stats["spec_draft_dispatches"] = self.draft.dispatches
            self.stats["spec_degraded"] = self.draft.degraded
            if not chunk_items and not spec_lanes:
                # everything degraded / out of headroom: keep the plain
                # pure-decode dispatch (still one per step)
                if plain_dec:
                    self._decode_batch_paged(
                        [it.request for it in plain_dec])
                return []
        n_pref = len(chunk_items)
        Lc, Cb, Ld = self._lanes(), self._chunk_len(), self._lanes()
        ctoks = np.zeros((Lc, Cb), np.int32)
        cstart = np.zeros(Lc, np.int32)
        clen = np.zeros(Lc, np.int32)
        for i, it in enumerate(chunk_items):
            r, s, n = it.request, it.request.prefill_done, it.chunk_tokens
            ctoks[i, :n] = r.tokens[s:s + n]
            cstart[i], clen[i] = s, n
        # verify lanes ride the SAME chunk half: [pending, d1..dK] at
        # the request's current context position against its own pages
        # (pre-reserved at admission, so no append — rejected target KV
        # is overwritten positionally by the next step's chunk)
        for v, (r, k_eff, d, pos) in enumerate(spec_lanes):
            i = n_pref + v
            ctoks[i, 0] = self.live[r.request_id]["next"]
            ctoks[i, 1:k_eff + 1] = d
            cstart[i], clen[i] = pos, k_eff + 1
        cpt = self._page_table_rows(
            [("req", it.request.request_id) for it in chunk_items]
            + [("req", r.request_id) for r, _, _, _ in spec_lanes],
            n_rows=Lc)
        dtoks = np.zeros(Ld, np.int32)
        dpos = np.zeros(Ld, np.int32)
        for i, it in enumerate(plain_dec):
            r = it.request
            dtoks[i] = self.live[r.request_id]["next"]
            dpos[i] = r.prompt_len + len(r.output_tokens) - 1
        dpt = self._page_table_rows(
            [("req", it.request.request_id) for it in plain_dec],
            n_rows=Ld)
        # ScheduleBatch -> ModelWorkerBatch -> ForwardBatch (§13): the
        # host-side arrays above lower in ONE device transfer, then the
        # single donated (sharded) dispatch consumes them — scheduling
        # state and page tables never live on device
        wb = ModelWorkerBatch(ctoks, cstart, clen, cpt, dtoks, dpos, dpt)
        fb = self._lower_batch(wb)
        with host_span(self._span["dispatch"]):
            if spec_lanes:
                nxt, cpred, self.pages = self._mixed_spec_fn(
                    self.params, self.pages, fb.chunk_tokens,
                    fb.chunk_start, fb.chunk_len, fb.chunk_page_table,
                    fb.dec_tokens, fb.dec_pos, fb.dec_page_table)
            else:
                nxt, self.pages = self._mixed_paged_fn(
                    self.params, self.pages, fb.chunk_tokens,
                    fb.chunk_start, fb.chunk_len, fb.chunk_page_table,
                    fb.dec_tokens, fb.dec_pos, fb.dec_page_table)
        if spec_lanes:
            nxt, cpred = self._fetch_result(nxt, cpred)
        else:
            nxt, = self._fetch_result(nxt)
        self.stats["model_dispatches"] += 1
        self.stats["fused_iterations"] += 1
        self.stats["fused_padded_tokens"] += (
            Lc * Cb + Ld - int(clen.sum()) - len(plain_dec))
        newly_prefilled: List[Request] = []
        for i, it in enumerate(chunk_items):
            r = it.request
            self.stats["prefilled_tokens"] += it.chunk_tokens
            if r.prefill_done + it.chunk_tokens >= r.prompt_len:
                # prefill emits the FIRST generated token
                tok = int(nxt[i])
                self.live[r.request_id]["next"] = tok
                r.output_tokens.append(tok)
                newly_prefilled.append(r)
        # --- verification (§14): chunk_pred[lane, j] is the target's
        # greedy prediction AFTER chunk token j, i.e. p_j. Accept d_j
        # iff d_j == p_{j-1}; with `a` leading accepts the step commits
        # d1..da + the target's correction p_a (= the plain path's next
        # token when a = 0 — greedy spec is token-exact by induction).
        for v, (r, k_eff, d, pos) in enumerate(spec_lanes):
            preds = cpred[n_pref + v]
            a = 0
            while a < k_eff and d[a] == int(preds[a]):
                a += 1
            # accepted drafts land now; complete_iteration then appends
            # its usual placeholder (the a+1-th committed token) which
            # step()'s overwrite loop sets to the correction p_a
            r.output_tokens.extend(d[:a])
            lv = self.live[r.request_id]
            lv["next"] = int(preds[a])
            lv["spec_prop"] = lv.get("spec_prop", 0) + k_eff
            lv["spec_acc"] = lv.get("spec_acc", 0) + a
            self.draft.commit(r.request_id, pos, a)
            self.stats["spec_proposed_tokens"] += k_eff
            self.stats["spec_accepted_tokens"] += a
            self.stats["spec_rejected_tokens"] += k_eff - a
        for i, it in enumerate(plain_dec):
            r = it.request
            self.live[r.request_id]["next"] = int(nxt[Lc + i])
        if dec_items:
            self.stats["decode_steps"] += len(dec_items)
            self.stats["decode_batches"] += 1
            self.stats["spec_verify_lanes"] += len(spec_lanes)
        return newly_prefilled

    def _observe_spec(self, r: Request, lv: Optional[Dict[str, Any]],
                      now: float) -> None:
        """Terminal speculative observation for one finished request:
        the per-request acceptance-rate histogram + a `spec` trace point
        (surfaced by RequestTrace.breakdown as informational keys)."""
        if not lv or not lv.get("spec_prop"):
            return
        prop, acc = lv["spec_prop"], lv["spec_acc"]
        if self.telemetry is not None:
            self.telemetry.registry.histogram(
                "engine_spec_acceptance",
                buckets=(0.1, 0.25, 0.5, 0.75, 0.9, 1.0),
                instance=self.econf.instance_id).observe(acc / prop)
        if r.trace is not None:
            r.trace.point("spec", now, proposed=prop, accepted=acc)

    def _decode_batch_paged(self, dec: List[Request]) -> None:
        """Slot decode (DESIGN.md §3): live requests fill the first B
        of max_batch_requests lanes; padding lanes write into the
        scratch page. One donated jit for every batch size — no cache
        concat, no per-request splits, no per-batch-size retraces."""
        B = len(dec)
        Bb = self._lanes()
        tokens = np.zeros(Bb, np.int32)
        pos = np.zeros(Bb, np.int32)
        for i, r in enumerate(dec):
            tokens[i] = self.live[r.request_id]["next"]
            # the token being fed sits at context position
            # prompt_len + (#output tokens already in the cache); the
            # first output token (from prefill) is not yet cached.
            pos[i] = r.prompt_len + len(r.output_tokens) - 1
        pt = self._page_table_rows(
            [("req", r.request_id) for r in dec], n_rows=Bb)
        # pure-decode steps ride the same host/device batch boundary as
        # the fused path: empty chunk half, one lowering, one dispatch
        wb = ModelWorkerBatch(np.zeros((0, 1), np.int32),
                              np.zeros(0, np.int32), np.zeros(0, np.int32),
                              np.zeros((0, self._pages_per_req), np.int32),
                              tokens, pos, pt)
        fb = self._lower_batch(wb)
        with host_span(self._span["dispatch"]):
            nxt, self.pages = self._decode_paged_fn(
                self.params, self.pages, fb.dec_tokens, fb.dec_pos,
                fb.dec_page_table)
        nxt, = self._fetch_result(nxt)
        for i, r in enumerate(dec):
            self.live[r.request_id]["next"] = int(nxt[i])
        self.stats["decode_only_lanes"] += Bb
        self.stats["decode_only_padded_lanes"] += Bb - B
        self.stats["decode_steps"] += B
        self.stats["decode_batches"] += 1
        self.stats["model_dispatches"] += 1

    def _decode_batch_dense(self, dec: List[Request]) -> None:
        """DENSE reference: rebuild the batch cache with O(B * S)
        concat/index copies every iteration (and retrace per batch
        size) — the cost the paged plane removes."""
        caches = _cache_concat(
            [self.live[r.request_id]["cache"] for r in dec])
        self.stats["cache_concat_calls"] += 1
        tokens = jnp.asarray(
            [self.live[r.request_id]["next"] for r in dec], jnp.int32)
        pos = jnp.asarray(
            [r.prompt_len + len(r.output_tokens) - 1 for r in dec],
            jnp.int32)
        nxt, caches = self._decode_fn(self.params, caches, tokens, pos)
        nxt = np.asarray(nxt)
        for i, r in enumerate(dec):
            self.live[r.request_id]["cache"] = _cache_index(caches, i)
            self.live[r.request_id]["next"] = int(nxt[i])
        self.stats["decode_steps"] += len(dec)
        self.stats["decode_batches"] += 1
        self.stats["model_dispatches"] += 1

    # ---- failure ---------------------------------------------------------------

    def attach_faults(self, faults,
                      breaker: Optional[CircuitBreaker] = None) -> None:
        """Wire the cluster's shared fault injector into this engine's
        fault points, plus a per-instance circuit breaker over the
        host-tier restore/prefetch path (only meaningful when the tier
        exists). Fault-free runs never call this, so every hook stays
        behind ``self.faults is not None``."""
        self.faults = faults
        if self.econf.host_capacity_tokens > 0:
            self._cb = breaker if breaker is not None else CircuitBreaker()

    def attach_telemetry(self, telemetry) -> None:
        """Bind this engine's stats surfaces into the shared telemetry
        registry (engine_* / sched_* / hoststore_* series labeled with
        the instance id) and register callback gauges over the live
        token accounting — evaluated only at export, so the step path
        pays nothing. Mirrors ``attach_faults``: never called on
        untelemetered runs."""
        inst = self.econf.instance_id
        self.telemetry = telemetry
        self.stats = telemetry.adopt(self.stats, "engine", instance=inst)
        sch = self.scheduler
        sch.telemetry = telemetry
        sch.stats = telemetry.adopt(sch.stats, "sched", instance=inst)
        if self.host_store is not None:
            self.host_store.stats = telemetry.adopt(
                self.host_store.stats, "hoststore", instance=inst)
        telemetry.gauge_fn("sched_used_tokens",
                           lambda s=sch: s.used_tokens, instance=inst)
        telemetry.gauge_fn("sched_host_used_tokens",
                           lambda s=sch: s.host_used_tokens,
                           instance=inst)
        telemetry.gauge_fn("sched_prefetch_reserved_tokens",
                           lambda s=sch: s.prefetch_reserved_tokens,
                           instance=inst)
        # SPMD plane (§13): per-shard pool occupancy. Every chip holds
        # a 1/chips slice of every live page, so each shard's occupancy
        # in tokens equals the pool's used pages x page_size (its BYTES
        # are 1/chips of that); reading through the engine keeps the
        # gauge live across fail()'s pool rebuild.
        if self.mesh is not None:
            for s in range(self.chips):
                telemetry.gauge_fn(
                    "engine_shard_pool_tokens",
                    lambda e=self: (e.pool.used_pages * e.pool.page_size
                                    // e.chips),
                    instance=inst, shard=s)

    def crash(self) -> None:
        """SILENT death (vs ``fail``, the oracle path): the data plane
        stops — live state gone, no more steps — but the scheduler's
        queues and the global scheduler's view are left stranded until
        the heartbeat detector declares this instance DEAD and the
        runtime recovers it through ``fail``."""
        self.failed = True
        self.live.clear()

    def fail(self) -> List[Request]:
        """Simulate instance death: drop all device state, return the
        in-flight requests for global re-scheduling."""
        self.failed = True
        self.live.clear()
        reqs = self.scheduler.drain()
        if self.paged:
            self._init_paged()      # fresh pool + re-hook the new tree
        else:
            self._init_dense()      # fresh pool + empty kv/state stores
        return reqs

    @property
    def depth(self) -> int:
        return self.scheduler.depth
