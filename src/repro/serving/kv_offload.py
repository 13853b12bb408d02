"""Hierarchical KV tiering — the host-memory offload tier (DESIGN.md §8/§9).

Device HBM is tier 0 (the paged pool); this module adds tier 1: plain
host RAM holding *demoted* KV. Under memory pressure the local
scheduler's eviction no longer drops a radix node's KV — it demotes it:
the node's pages are gathered device->host in ONE batched transfer and
parked here, indexed by the node's CONTENT-ADDRESSED path key at token
granularity. A later cache hit on a demoted prefix restores it
host->device into freshly allocated pages (one batched scatter folded
into the engine's fused step) instead of recomputing the prefill — a
bandwidth-bound DMA versus a compute-bound recompute
(CostModel.restore_time vs prefill_time). Because entries are keyed by
token-path content (DESIGN.md §9), they are PORTABLE: tier-to-tier
migration ships an entry to another instance's HostKVStore, where the
target's own restore path materializes it.

Split of responsibilities:

  * ``LocalScheduler`` owns the tier POLICY: which spans are
    host-resident, their hit-rate-weighted retention order, and the
    host token budget (``LocalSchedulerConfig.host_capacity_tokens``).
  * ``HostKVStore`` (here) owns the BYTES: numpy KV spans keyed by path
    key, mirroring the page-pool pytree structure per layer. It has no
    eviction logic of its own — single-authority capacity lives with
    the scheduler, so the two can be reconciled exactly
    (``ClusterRuntime.check_invariants``). Each entry also pins the
    local node id that owns it, so a path-digest collision can never
    hand one prefix another prefix's KV (readers verify the owner).
  * ``PagedHostTier`` (here) is the DATA MOVER the scheduler drives:
    ``demote_many`` DOUBLE-BUFFERS a whole eviction plan — it issues
    one bucketed device gather immediately (the gather snapshots the
    pages into fresh device buffers, so releasing the pages afterwards
    is safe: execution order follows dispatch order on the device
    stream) and defers the device->host copy until ``drain``, which the
    engine calls AFTER enqueueing the step's model dispatch — the DMA
    overlaps compute. Reads that need the bytes earlier (restore
    chains, migration export, reconciliation) force a drain first;
    ``Engine.stats['demote_overlap_frac']`` reports how often the copy
    actually hid behind compute. ``drop`` frees host bytes (or cancels
    a still-pending job); ``ingest``/``export`` are the migration
    endpoints.

Entries are TOKEN-granular (arrays of shape [span, KH, D] per layer
leaf, unpacked from the pool's [KH, R, 128] page blocks by
``models.attention.pages_to_tokens``), so demote/restore/migrate
boundaries are independent of page alignment; the engine's restore
scatter maps tokens back onto (page, slot) pairs of the destination
request's table.

All numpy buffers are C-contiguous host arrays ("pinned" in the TPU
runtime sense: jax device_get lands them in transfer-friendly memory);
the KV round-trips bit-exactly, which tests/test_kv_offload.py checks
against the dense oracle.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.radix_tree import PathKey
from ..models.attention import pages_to_tokens

Pytree = Any


@dataclass
class HostEntry:
    """One demoted radix-node span: tokens [start, start+length) of the
    node's root->node sequence, as host numpy arrays per layer leaf.
    ``node_id`` pins the owning LOCAL node (collision guard: a path key
    names content, the node id disambiguates the astronomically rare
    digest collision within one instance)."""
    key: PathKey
    start: int                       # absolute token depth of the span
    kv: Pytree                       # {pj: {gg: {"k"/"v": np [L, KH, D]}}}
    length: int = 0
    node_id: int = -1

    def slice(self, lo: int, hi: int) -> Pytree:
        """Token-subrange [lo, hi) of this span, in ABSOLUTE depth."""
        a, b = lo - self.start, hi - self.start
        assert 0 <= a <= b <= self.length, (lo, hi, self.start, self.length)
        return _tree_map(lambda x: x[a:b], self.kv)


def _tree_map(fn, tree: Pytree) -> Pytree:
    if isinstance(tree, dict):
        return {k: _tree_map(fn, v) for k, v in tree.items()}
    return fn(tree)


def tree_leaves(tree: Pytree, prefix: Tuple = ()) -> List[Tuple[Tuple, Any]]:
    if isinstance(tree, dict):
        out = []
        for k in tree:
            out.extend(tree_leaves(tree[k], prefix + (k,)))
        return out
    return [(prefix, tree)]


class HostKVStore:
    """Host-RAM byte store for demoted KV, keyed by content-addressed
    path key. Capacity is enforced by the LocalScheduler (single
    authority); the store only tracks usage so the two layers can be
    reconciled."""

    def __init__(self):
        self.entries: Dict[PathKey, HostEntry] = {}
        self.used_tokens = 0
        self.stats = {"puts": 0, "drops": 0, "splits": 0, "ingests": 0,
                      "reads": 0, "prefetch_reads": 0}

    def __contains__(self, key) -> bool:
        return key in self.entries

    def __len__(self) -> int:
        return len(self.entries)

    def put(self, key, start: int, kv: Pytree, length: int,
            node_id: int = -1) -> None:
        assert key not in self.entries, f"span {key} already demoted"
        self.entries[key] = HostEntry(key, start, kv, length, node_id)
        self.used_tokens += length
        self.stats["puts"] += 1

    def get(self, key) -> Optional[HostEntry]:
        return self.entries.get(key)

    def read_span(self, key, node_id: int, lo: int, hi: int, *,
                  speculative: bool = False) -> Optional[Pytree]:
        """Verified read of tokens [lo, hi) under ``key``: None when
        the entry is missing, owned by a different node (digest
        collision — never hand out another prefix's KV), or does not
        cover the range. Reads are POLICY-NEUTRAL: no recency or heat
        update happens here — the scheduler decides what counts as a
        hit, and a ``speculative`` prefetch read never does (it only
        shows up in its own counter)."""
        e = self.entries.get(key)
        if (e is None or (node_id >= 0 and e.node_id != node_id)
                or e.start > lo or e.start + e.length < hi):
            return None
        self.stats["prefetch_reads" if speculative else "reads"] += 1
        return e.slice(lo, hi)

    def drop(self, key) -> int:
        e = self.entries.pop(key, None)
        if e is None:
            return 0
        self.used_tokens -= e.length
        self.stats["drops"] += 1
        return e.length

    def clear(self) -> None:
        self.entries.clear()
        self.used_tokens = 0

    def on_split(self, head, tail) -> None:
        """Radix-node split hook. The TAIL keeps the pre-split path key
        (its end boundary is unchanged), so the existing entry's key now
        names the tail: the tokens past the cut stay under it, while
        the head's part is rekeyed under the head's new (shallower) key
        — numpy slicing, no device traffic. Mirrors the scheduler's
        LRU rekey exactly (same keys, same collision condition)."""
        e = self.entries.get(tail.path_key)
        if e is None or e.node_id != head.node_id:
            return                    # no entry, or a collided key's entry
        boundary = head.depth_tokens()           # absolute, post-split
        keep = boundary - e.start
        if keep <= 0:
            e.node_id = tail.node_id             # fully past the cut
            return
        if keep >= e.length:
            # span ends at/before the cut: the whole entry belongs to
            # the head — move it under the head's new key
            del self.entries[tail.path_key]
            e.key = head.path_key
            e.node_id = head.node_id
            if head.path_key in self.entries:    # digest collision
                self.used_tokens -= e.length     # (mirrors scheduler drop)
                self.stats["drops"] += 1
            else:
                self.entries[head.path_key] = e
            return
        head_kv = _tree_map(lambda x: x[:keep], e.kv)
        e.kv = _tree_map(lambda x: x[keep:], e.kv)
        head_len = keep
        e.length -= keep
        e.start = boundary
        e.node_id = tail.node_id
        if head.path_key in self.entries:        # digest collision
            self.used_tokens -= head_len
            self.stats["drops"] += 1
        else:
            self.entries[head.path_key] = HostEntry(
                head.path_key, boundary - keep, head_kv, head_len,
                head.node_id)
        self.stats["splits"] += 1

    def check_invariants(self) -> None:
        total = 0
        for key, e in self.entries.items():
            assert e.key == key
            assert e.length >= 0 and e.start >= 0
            for _, leaf in tree_leaves(e.kv):
                assert isinstance(leaf, np.ndarray), "host tier must hold numpy"
                assert leaf.shape[0] == e.length, (leaf.shape, e.length)
            total += e.length
        assert total == self.used_tokens, (total, self.used_tokens)


class PagedHostTier:
    """Data mover between an Engine's paged device plane and a
    HostKVStore. The LocalScheduler calls ``demote_many`` with the
    eviction plan's nodes, ``drop`` on host-capacity overflow, and
    ``export``/``ingest`` for tier-to-tier migration."""

    carries_bytes = True     # vs AccountingHostTier: payloads are real

    def __init__(self, engine, store: HostKVStore):
        self.engine = engine
        self.store = store
        # double-buffered demotes: gathers already ISSUED on device but
        # not yet copied to host. Each record: (gathered device pytree,
        # jobs, dispatch count at issue time); jobs may be cancelled by
        # ``drop`` before the copy lands.
        self._pending: List[dict] = []

    # ---- demote: device -> host (double-buffered) --------------------------

    def demote_many(self, nodes: Sequence) -> Dict[PathKey, int]:
        """Demote every node in an eviction plan whose KV is actually
        materialized in the pool: ONE bucketed device gather over all
        their pages is issued NOW (snapshotting them into fresh device
        buffers), the device->host copy is deferred to ``drain`` so it
        overlaps the step's model dispatch. Releases the nodes' pool
        tables either way (the device tier is gone after eviction —
        safe because the gather was dispatched first and the device
        stream executes in dispatch order). Returns
        {path_key: demoted_token_count} for spans now (or about to be)
        host-resident."""
        if self._pending and any(
                job[0] == n.path_key
                for rec in self._pending for job in rec["jobs"]
                for n in nodes):
            self.drain()              # re-demotion check needs those bytes
        eng, pool = self.engine, self.engine.pool
        ps = pool.page_size
        jobs: List[Tuple[PathKey, int, int, int, int, int]] = []
        all_pages: List[int] = []
        out: Dict[PathKey, int] = {}
        for node in nodes:
            key = ("node", node.path_key)
            t = pool.tables.get(key)
            if t is None:
                continue                       # KV never materialized
            end = node.depth_tokens()
            start = end - len(node.tokens)
            cov = min(t.num_tokens, end)       # table may be trimmed
            prev = self.store.get(node.path_key)
            if prev is not None and prev.node_id == node.node_id:
                # re-demotion of a restored-then-evicted node: the host
                # copy is still valid (KV is a pure function of the
                # token prefix) — no new transfer needed.
                out[node.path_key] = prev.length
                pool.release(key)
                continue
            if prev is not None:
                # digest collision with a foreign entry: drop, never
                # overwrite another prefix's KV
                pool.release(key)
                continue
            if cov > start:
                faults = getattr(eng, "faults", None)
                if faults is not None and faults.dma_fails("demote"):
                    # injected device->host DMA failure: the transfer is
                    # lost, so the span DROPS instead of demoting — the
                    # scheduler sees no demotion and the eviction
                    # notification reports the span as gone
                    pool.release(key)
                    continue
                p0, p1 = start // ps, -(-cov // ps)
                jobs.append((node.path_key, node.node_id, start, cov,
                             len(all_pages), p1 - p0))
                all_pages.extend(t.pages[p0:p1])
                out[node.path_key] = cov - start
            pool.release(key)
        if jobs:
            gathered, n = eng.gather_pages_device(all_pages)
            self._pending.append({
                "gathered": gathered, "n": n, "jobs": jobs,
                "cancelled": set(),
                "dispatches_at_issue": eng.stats["model_dispatches"]})
        return out

    def pending_has(self, key) -> bool:
        """Is this span's demote DMA still in flight (issued, not yet
        landed host-side)?"""
        return any(job[0] == key and key not in rec["cancelled"]
                   for rec in self._pending for job in rec["jobs"])

    def drain(self) -> None:
        """Land every pending demote's bytes in the store (the deferred
        device->host copy). Called by the engine at the END of a step —
        after the model dispatch was enqueued, so the copy overlapped
        compute — or forced earlier by a read that needs the bytes."""
        pending, self._pending = self._pending, []
        eng = self.engine
        ps, head_dim = eng.pool.page_size, eng.model_cfg.head_dim
        for rec in pending:
            # device->host landing of the demote gather. On an SPMD
            # submesh each chip ships only its own KV slice (head/slot
            # shard) over its own host link — the copy here assembles
            # the per-shard pieces
            arr = _tree_map(lambda a: np.asarray(a)[:rec["n"]],
                            rec["gathered"])
            demoted = 0
            for key, node_id, start, cov, ofs, npg in rec["jobs"]:
                if key in rec["cancelled"]:
                    continue
                base = (start // ps) * ps
                span = _tree_map(
                    lambda x: np.ascontiguousarray(
                        pages_to_tokens(x[ofs:ofs + npg], head_dim)
                        [start - base:cov - base]),
                    arr)
                self.store.put(key, start, span, cov - start,
                               node_id=node_id)
                demoted += cov - start
            eng.stats["demoted_tokens"] += demoted
            eng.stats["demote_batches"] += 1
            if eng.stats["model_dispatches"] > rec["dispatches_at_issue"]:
                eng.stats["demote_batches_overlapped"] += 1
        # demote_overlap_frac is a derived StatsDict key on Engine.stats
        # — computed at read time, never recomputed in this drain loop

    # ---- drop: host entry dies --------------------------------------------

    def drop(self, key) -> None:
        for rec in self._pending:
            for job in rec["jobs"]:
                if job[0] == key:
                    rec["cancelled"].add(key)
        self.store.drop(key)

    # ---- migration endpoints (DESIGN.md §9) --------------------------------

    def export(self, node, lo: int, hi: int) -> Optional[Pytree]:
        """Slice this node's host entry for tokens [lo, hi) — the
        migration source side. Forces a drain (the bytes must exist to
        ship) and verifies entry ownership (collision guard)."""
        if self._pending:
            self.drain()
        e = self.store.get(node.path_key)
        if (e is None or e.node_id != node.node_id
                or e.start > lo or e.start + e.length < hi):
            return None
        return e.slice(lo, hi)

    def ingest(self, node, start: int, length: int, payload: Pytree,
               offset: int) -> None:
        """Land a migrated span [start, start+length) for ``node`` —
        the migration target side. ``payload`` covers the shipped piece
        from ``offset`` relative tokens in; the copy models the DCN
        transfer landing in this host's RAM."""
        if payload is None:
            return
        if self._pending:
            self.drain()
        kv = _tree_map(
            lambda x: np.ascontiguousarray(x[offset:offset + length]),
            payload)
        self.store.put(node.path_key, start, kv, length,
                       node_id=node.node_id)
        self.store.stats["ingests"] += 1
