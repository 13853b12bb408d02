"""SPMD multi-chip serving data plane (DESIGN.md §13).

On an emulated >=4-device CPU mesh (conftest forces
--xla_force_host_platform_device_count=8) the tensor-parallel engine
must be token-exact against the single-device dense oracle across
randomized fused mixed schedules — CoW splits, evict/demote, restore,
speculative prefetch, cluster migration — while issuing exactly one
donated model dispatch per scheduling step, and its pooled device KV
capacity must scale with the submesh at fixed per-chip HBM.
"""

import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, reduced
from repro.core.request import Request
from repro.models import zoo
from repro.serving.cluster import ClusterRuntime
from repro.serving.engine import Engine, EngineConfig

needs4 = pytest.mark.skipif(len(jax.devices()) < 4,
                            reason="needs >=4 emulated devices")


@pytest.fixture(scope="module")
def small_model():
    cfg = dataclasses.replace(reduced(ARCHS["smollm-360m"]), n_layers=2,
                              dtype="float32")
    api = zoo.build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    return cfg, api, params


def _econf(chips, device_capacity=8192, **kw):
    """capacity_tokens is PER CHIP — fixing the DEVICE capacity keeps
    scheduler decisions (admission, eviction, demotion) identical
    across TP degrees, which exactness comparisons require."""
    assert device_capacity % max(chips, 1) == 0
    base = dict(max_context=96, chunk_size=16, max_batch_tokens=96,
                max_batch_requests=16,
                capacity_tokens=device_capacity // max(chips, 1),
                page_size=16, chips_per_instance=chips)
    base.update(kw)
    return EngineConfig(**base)


def _drive(eng, waves, max_iters=2000):
    done, now = [], 0.0
    total = sum(len(rs) for _, rs in waves)
    for it in range(max_iters):
        for at, rs in waves:
            if at == it:
                for r in rs:
                    eng.scheduler.enqueue(r, now)
        done += eng.step(now)
        now += 0.01
        if len(done) == total and it >= max(at for at, _ in waves):
            break
    assert len(done) == total, "requests did not finish"
    return done


def _waves(cfg, seed, n1=3, n2=4, tail=(4, 20), new=(3, 8)):
    """Shared-prefix request waves (page-aligned and CoW boundaries)."""
    rng = np.random.default_rng(seed)
    shared_len = int(rng.choice([16, 23, 32, 41]))
    shared = tuple(rng.integers(1, cfg.vocab_size, shared_len).tolist())

    def wave(n, s2):
        rr = np.random.default_rng(s2)
        return [Request(tokens=shared
                        + tuple(rr.integers(1, cfg.vocab_size,
                                            int(rr.integers(*tail)))
                                .tolist()),
                        max_new_tokens=int(rr.integers(*new)))
                for _ in range(n)]

    return [(0, wave(n1, seed + 10)), (4, wave(n2, seed + 20))]


def _outs(done):
    return {(tuple(r.tokens), r.max_new_tokens): list(r.output_tokens)
            for r in done}


@needs4
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sharded_matches_dense_oracle(small_model, seed):
    """chips=4 fused paged plane vs the single-device DENSE reference:
    randomized mixed schedules must be token-identical."""
    cfg, api, params = small_model
    outs = {}
    for chips, paged in ((1, False), (4, True)):
        eng = Engine(cfg, params, _econf(chips, paged=paged))
        if chips > 1:
            assert eng.mesh is not None and eng.fused
        done = _drive(eng, _waves(cfg, seed))
        if chips > 1:
            assert eng.stats["fused_iterations"] > 0
            assert eng.stats["reused_tokens"] > 0, "cache never hit"
            eng.pool.check_invariants()
        outs[chips] = _outs(done)
    assert outs[4] == outs[1]


@needs4
def test_sharded_offload_restore_prefetch_exact(small_model, profile):
    """Tight pool + host tier + prefetch budget: evictions demote KV
    device->host per shard, later hits restore/prefetch it back — the
    4-chip engine must stay token-exact vs the 1-chip paged engine at
    the same device capacity, with the DMA actually exercised."""
    cfg, api, params = small_model
    rng = np.random.default_rng(7)
    shared = tuple(rng.integers(1, cfg.vocab_size, 23).tolist())

    def drain(eng, done, target, now, max_iters=3000):
        for _ in range(max_iters):
            if len(done) >= target:
                return now
            done += eng.step(now)
            now += 0.01
        raise RuntimeError("engine did not converge")

    outs, engs = {}, {}
    with profile.trace():
        for chips in (1, 4):
            # tight pool (160 device tokens) so the thrash wave evicts the
            # warm shared prefix (demote), re-hits restore/prefetch it back
            eng = Engine(cfg, params, _econf(
                chips, device_capacity=160, max_context=64, page_size=8,
                max_batch_tokens=64, max_batch_requests=4,
                host_capacity_tokens=4096, prefetch_budget_tokens=256,
                instance_id=chips))
            done, now = [], 0.0
            rr = np.random.default_rng(70)
            warm = [Request(tokens=shared
                            + tuple(rr.integers(1, cfg.vocab_size, 8)
                                    .tolist()), max_new_tokens=3)
                    for _ in range(3)]
            for r in warm:
                eng.scheduler.enqueue(r, now)
            now = drain(eng, done, len(warm), now)
            thrash = [Request(tokens=tuple(
                          np.random.default_rng(700 + i)
                          .integers(1, cfg.vocab_size, 45).tolist()),
                          max_new_tokens=6) for i in range(6)]
            for r in thrash:
                eng.scheduler.enqueue(r, now)
            for _ in range(6):              # fill every lane, force evicts
                done += eng.step(now)
                now += 0.01
            # re-hits enqueue while lanes are full -> they WAIT with their
            # shared prefix host-resident -> speculative prefetch kicks in
            rehit = [Request(tokens=r.tokens,
                             max_new_tokens=r.max_new_tokens)
                     for r in warm]
            for r in rehit:
                eng.scheduler.enqueue(r, now)
            now = drain(eng, done, len(warm) + len(thrash) + len(rehit), now)
            outs[chips] = _outs(done)
            engs[chips] = eng
    e4 = engs[4]
    assert e4.stats["demoted_tokens"] > 0, "no demote traffic"
    assert e4.stats["restored_tokens"] > 0, "no restore traffic"
    assert outs[4] == outs[1]
    # the mesh engine (instance_id 4) lowers its batches and fetches
    # its results inside the program's profiler spans
    assert {"preble.engine.lower.4", "preble.engine.fetch.4"} <= {
        name for name, _, _ in profile.spans()}


@needs4
def test_exactly_one_dispatch_per_step(small_model):
    """The host/device batch split ships ONE lowered batch and ONE
    donated sharded dispatch per scheduling step, mixed or pure-decode;
    idle steps dispatch nothing."""
    cfg, api, params = small_model
    eng = Engine(cfg, params, _econf(4))
    rng = np.random.default_rng(3)
    reqs = [Request(tokens=tuple(rng.integers(1, cfg.vocab_size, 20 + i)
                                 .tolist()), max_new_tokens=5)
            for i in range(5)]
    now = 0.0
    for r in reqs[:3]:
        eng.scheduler.enqueue(r, now)
    finished = 0
    for it in range(300):
        if it == 2:
            for r in reqs[3:]:
                eng.scheduler.enqueue(r, now)
        before = eng.stats["model_dispatches"]
        done = eng.step(now)
        finished += len(done)
        delta = eng.stats["model_dispatches"] - before
        assert delta == (1 if eng.depth > 0 or done else 0), \
            f"step {it}: {delta} dispatches"
        now += 0.01
        if finished == len(reqs):
            break
    assert finished == len(reqs)
    assert eng.stats["model_dispatches"] == eng.stats["iterations"]


@needs4
def test_pool_capacity_scales_with_chips(small_model):
    """Fixed PER-CHIP capacity: aggregate pooled device KV tokens —
    scheduler budget and physical pool alike — scale linearly with the
    submesh, and each chip holds a 1/tp slice of every page."""
    cfg, api, params = small_model
    # head_dim 64: a 16-token page is 8 rows of 128 lanes, which 2 and
    # 4 chips divide
    cfg = dataclasses.replace(cfg, head_dim=64)
    params = zoo.build(cfg).init(jax.random.PRNGKey(0))
    pools = {}
    for chips in (1, 2, 4):
        ec = _econf(chips, device_capacity=2048 * chips)  # 2048/chip
        assert ec.capacity_tokens == 2048
        assert ec.device_capacity_tokens == 2048 * chips
        eng = Engine(cfg, params, ec)
        assert eng.scheduler.config.capacity_tokens == 2048 * chips
        pools[chips] = eng.pool.num_pages * ec.page_size
        if chips > 1:
            leaf = jax.tree.leaves(eng.pages)[0]
            assert leaf.sharding.spec == P(None, None, "model", None), \
                "KH=1 pool must slot-shard (GQA fallback)"
            shards = leaf.addressable_shards
            assert len(shards) == chips
            # a page's rows (token slots) split 1/chips; page count NOT
            # split (pooling)
            assert shards[0].data.shape[2] == leaf.shape[2] // chips
            assert shards[0].data.shape[2] * 128 // 64 == \
                ec.page_size // chips
            assert shards[0].data.shape[0] == leaf.shape[0]
    base = pools[1] - 2 * 16 * 16 - 16   # scratch+headroom pages fixed
    assert pools[2] - pools[1] == 2048
    assert pools[4] - pools[2] == 2 * 2048
    assert base == 2048


@needs4
def test_gqa_head_sharding_when_divisible(small_model):
    """When kv_heads DOES divide the TP degree the pool shards
    head-wise (Megatron attention) — and stays token-exact."""
    cfg, _, _ = small_model
    cfg2 = dataclasses.replace(cfg, n_heads=2, n_kv_heads=2)
    api2 = zoo.build(cfg2)
    params2 = api2.init(jax.random.PRNGKey(1))
    outs = {}
    for chips in (1, 2):
        eng = Engine(cfg2, params2, _econf(chips))
        if chips > 1:
            leaf = jax.tree.leaves(eng.pages)[0]
            assert leaf.sharding.spec == P(None, "model", None, None)
        outs[chips] = _outs(_drive(eng, _waves(cfg2, 11)))
    assert outs[2] == outs[1]


@needs4
def test_kernel_runs_per_head_shard(small_model, monkeypatch):
    """GSPMD cannot partition the Pallas paged-decode kernel: on a
    head-sharded submesh it runs per shard under shard_map (interpret
    mode here) and stays token-exact vs the 1-chip gather path."""
    from functools import partial

    from repro.kernels import paged_attention as kernel_mod
    from repro.models import attention

    cfg, _, _ = small_model
    cfg2 = dataclasses.replace(cfg, n_heads=2, n_kv_heads=2)
    api2 = zoo.build(cfg2)
    params2 = api2.init(jax.random.PRNGKey(1))
    ref = _outs(_drive(Engine(cfg2, params2, _econf(1)), _waves(cfg2, 5)))
    monkeypatch.setattr(attention, "auto_paged_impl", lambda: "pallas")
    traced = []
    interpret = partial(kernel_mod.paged_decode_attention, interpret=True)

    def kernel(q, *args):
        traced.append(q.shape)      # per-shard: one of the two heads
        return interpret(q, *args)

    monkeypatch.setattr(kernel_mod, "paged_decode_attention", kernel)
    eng = Engine(cfg2, params2, _econf(2))
    assert jax.tree.leaves(eng.pages)[0].sharding.spec == \
        P(None, "model", None, None)
    assert _outs(_drive(eng, _waves(cfg2, 5))) == ref
    assert traced and all(shape[1] == 1 for shape in traced)


@needs4
def test_heterogeneous_cluster_with_migration(small_model):
    """Mesh-of-meshes: a [4,1]-chip cluster (disjoint submeshes,
    per-instance cost models, aggregate capacities) finishes the same
    workload token-exactly as a homogeneous 1-chip cluster, survives a
    drain-driven host-tier migration, and keeps every cross-layer
    invariant."""
    cfg, api, params = small_model
    ec = EngineConfig(max_context=96, chunk_size=16, max_batch_tokens=96,
                      max_batch_requests=8, capacity_tokens=2048,
                      page_size=16, host_capacity_tokens=8192)
    rng = np.random.default_rng(5)
    shared = tuple(rng.integers(1, cfg.vocab_size, 24).tolist())

    def reqs():
        rr = np.random.default_rng(9)
        return [Request(tokens=shared
                        + tuple(rr.integers(1, cfg.vocab_size, 8 + i)
                                .tolist()),
                        max_new_tokens=4, arrival_time=0.005 * i)
                for i in range(8)]

    outs = {}
    for chips in ([4, 1], None):
        cl = ClusterRuntime(cfg, params, num_instances=2, engine_cfg=ec,
                            chips_per_instance=chips)
        if chips is not None:
            # aggregate capacity + per-chips cost model registered
            assert cl.gs.instances[0].capacity_tokens == 4 * 2048
            assert cl.gs.instances[1].capacity_tokens == 2048
            cm0 = cl.gs.instances[0].cost_model
            cm1 = cl.gs.instances[1].cost_model
            assert cm0.prefill_a * 4 == pytest.approx(cm1.prefill_a)
            meshes = [e.mesh for e in cl.engines.values()]
            assert meshes[0] is not None and meshes[1] is None
        done = list(cl.run(reqs(), dt=0.01))
        cl.check_invariants()
        outs[repr(chips)] = _outs(done)
        if chips is not None:
            # graceful drain migrates the 4-chip host tier out and the
            # survivor keeps serving
            cl.drain_instance(0, 1.0)
            more = Request(tokens=shared + (5, 6, 7), max_new_tokens=3)
            now = 1.0
            assert cl.submit(more, now) == 1   # only survivor
            for _ in range(200):
                cl.step(now)
                now += 0.01
                if len(cl.finished) == len(done) + 1:
                    break
            assert len(cl.finished) == len(done) + 1
            cl.check_invariants()
    assert outs["[4, 1]"] == outs["None"]
