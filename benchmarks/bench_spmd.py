"""SPMD data-plane smoke bench (DESIGN.md §13) — the `make shard-smoke`
gate.

Runs the SAME shared-prefix workload through real engine forwards at
TP degrees 1 / 2 / 4 on an emulated CPU mesh, with the per-chip pool
FIXED, and fails loudly unless:

  * every run is token-exact against the single-device DENSE oracle
    (the fused sharded plane must not change a single sampled token);
  * the fused plane issues EXACTLY 1.0 model dispatches per engine
    iteration at every TP degree (the host/device batch split ships
    one lowered batch + one donated dispatch per step);
  * aggregate device-pool KV tokens scale linearly with the mesh size
    at fixed per-chip HBM (PRISM-style pooling: each chip holds a
    1/chips slice of every page).

Prints the per-run table plus the per-shard resident pool tokens (via
the §12 telemetry registry); results land in
results/bench/bench_spmd.{csv,json}.
"""

from __future__ import annotations

import os

# the emulated mesh must exist before jax initializes its backends
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import dataclasses
import json

import jax
import numpy as np

from repro.configs import ARCHS, reduced
from repro.core.request import Request
from repro.models import zoo
from repro.serving.engine import Engine, EngineConfig
from repro.serving.telemetry import Telemetry

from .common import RESULTS_DIR, emit, timer

PER_CHIP_TOKENS = 2048
CHIPS = (1, 2, 4)


def _econf(chips, paged=None):
    return EngineConfig(max_context=96, chunk_size=16, max_batch_tokens=96,
                        max_batch_requests=16,
                        capacity_tokens=PER_CHIP_TOKENS, page_size=16,
                        paged=paged, chips_per_instance=chips)


def _waves(cfg, seed=0):
    rng = np.random.default_rng(seed)
    shared = tuple(rng.integers(1, cfg.vocab_size, 24).tolist())

    def wave(n, s2):
        rr = np.random.default_rng(s2)
        return [Request(tokens=shared
                        + tuple(rr.integers(1, cfg.vocab_size,
                                            int(rr.integers(6, 24)))
                                .tolist()),
                        max_new_tokens=int(rr.integers(3, 7)))
                for _ in range(n)]

    return [(0, wave(4, seed + 1)), (4, wave(4, seed + 2))]


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
    assert len(done) == total, "bench workload did not finish"
    return done


def _outs(done):
    return {(tuple(r.tokens), r.max_new_tokens): list(r.output_tokens)
            for r in done}


def main() -> None:
    assert len(jax.devices()) >= max(CHIPS), (
        f"need {max(CHIPS)} emulated devices, have {len(jax.devices())}")
    cfg = dataclasses.replace(reduced(ARCHS["smollm-360m"]), n_layers=2,
                              dtype="float32")
    api = zoo.build(cfg)
    params = api.init(jax.random.PRNGKey(0))

    # single-device dense reference: the exactness oracle
    oracle_eng = Engine(cfg, params, _econf(1, paged=False))
    with timer() as t_oracle:
        oracle = _outs(_drive(oracle_eng, _waves(cfg)))

    rows, shard_rows, pool_tokens = [], [], {}
    for chips in CHIPS:
        ec = _econf(chips)
        tel = Telemetry()
        eng = Engine(cfg, params, ec)
        eng.attach_telemetry(tel)
        with timer() as t:
            outs = _outs(_drive(eng, _waves(cfg)))

        # ---- gates ------------------------------------------------------
        assert outs == oracle, (
            f"chips={chips}: sharded fused plane diverged from the "
            f"single-device dense oracle")
        dpi = eng.stats["model_dispatches"] / max(eng.stats["iterations"], 1)
        assert dpi == 1.0, (
            f"chips={chips}: {dpi:.3f} model dispatches/iteration "
            f"(the batch split must ship exactly one)")
        toks = eng.pool.num_pages * ec.page_size
        pool_tokens[chips] = toks
        if chips > 1:
            grew = toks - pool_tokens[1]
            want = (chips - 1) * PER_CHIP_TOKENS
            assert grew == want, (
                f"chips={chips}: device pool grew {grew} tokens over "
                f"1-chip, expected {want} (capacity must pool)")

        rows.append({
            "chips": chips, "wall_s": t.s,
            "dispatches_per_iter": dpi,
            "device_pool_tokens": toks,
            "per_chip_tokens": PER_CHIP_TOKENS,
            "reused_tokens": eng.stats["reused_tokens"],
        })
        for s in range(chips if chips > 1 else 0):   # no shards w/o mesh
            g = tel.registry.get("engine_shard_pool_tokens",
                                 instance=ec.instance_id, shard=s)
            shard_rows.append({
                "chips": chips, "shard": s,
                "pool_tokens": (g if g is not None else 0),
            })

    emit("bench_spmd", rows)
    emit("bench_spmd_shards", shard_rows)
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, "bench_spmd.json"), "w") as f:
        json.dump({"config": {"per_chip_tokens": PER_CHIP_TOKENS,
                              "chips": list(CHIPS),
                              "oracle_wall_s": t_oracle.s},
                   "rows": rows, "shards": shard_rows,
                   "gates": ["token_exact_vs_dense_oracle",
                             "one_dispatch_per_iteration",
                             "pool_tokens_scale_with_mesh"]},
                  f, indent=2)
    print("shard-smoke gates passed: exactness, 1.0 dispatches/iter, "
          "pooled capacity scaling")


if __name__ == "__main__":
    main()
