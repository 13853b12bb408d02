"""The program's host spans on the profiler's clock
(``serving.telemetry.HOST_SPANS``) and the decode-only padding
counters, on a reduced one-replica cluster."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.core.request import Request
from repro.models import zoo
from repro.serving.cluster import ClusterRuntime
from repro.serving.engine import Engine, EngineConfig
from repro.serving.telemetry import HOST_SPANS

LANES = 4


@pytest.fixture(scope="module")
def small_model():
    cfg = dataclasses.replace(reduced(ARCHS["smollm-360m"]), n_layers=2,
                              dtype="float32")
    return cfg, zoo.build(cfg).init(jax.random.PRNGKey(0))


def _econf():
    return EngineConfig(max_context=64, page_size=8, chunk_size=16,
                        max_batch_requests=LANES, max_batch_tokens=64,
                        capacity_tokens=1024)


def _prompts(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, cfg.vocab_size, 12).tolist()
    return [Request(tokens=tuple(shared + rng.integers(
                        1, cfg.vocab_size, 10).tolist()), max_new_tokens=4)
            for _ in range(n)]


def _inside(spans, name, lo, hi):
    return [s for s in spans if s[0] == name and lo <= s[1] and s[2] <= hi]


def test_engine_and_e2_spans(small_model, profile):
    cfg, params = small_model
    cl = ClusterRuntime(cfg, params, 1, engine_cfg=_econf())
    cl.warmup()
    reqs = _prompts(cfg, 3)
    with profile.trace():
        for r in reqs:
            cl.submit(r, 0.0)
        for i in range(40):
            cl.step(0.01 * i)
            if len(cl.finished) == len(reqs):
                break
    assert len(cl.finished) == len(reqs)
    spans = profile.spans()
    names = {n for n, _, _ in spans}
    assert {n if n == "preble.e2.schedule" else n.rsplit(".", 1)[0]
            for n in names} == set(HOST_SPANS)
    assert all(n == "preble.e2.schedule" or n.endswith(".0")
               for n in names)
    assert sum(n == "preble.e2.schedule" for n, _, _ in spans) == len(reqs)
    steps = [s for s in spans if s[0] == "preble.engine.step.0"]
    dispatched = 0
    for _, lo, hi in steps:
        disp = _inside(spans, "preble.engine.dispatch.0", lo, hi)
        if not disp:
            continue
        dispatched += 1
        fetch = _inside(spans, "preble.engine.fetch.0", lo, hi)
        lower = _inside(spans, "preble.engine.lower.0", lo, hi)
        assert len(disp) == len(fetch) == len(lower) == 1
        # lowered, then dispatched, then blocked on the result
        assert lower[0][2] <= disp[0][1] and disp[0][2] <= fetch[0][1]
    # one step per token after the first, plus the prefills
    assert dispatched >= 4
    # every engine span lies inside a step span
    for name, lo, hi in spans:
        if name.startswith("preble.engine.") and \
                name != "preble.engine.step.0":
            assert any(s <= lo and hi <= e for _, s, e in steps), name


def test_decode_only_padded_lanes(small_model):
    cfg, params = small_model
    eng = Engine(cfg, params, _econf())
    r = _prompts(cfg, 1)[0]
    eng.scheduler.enqueue(r, 0.0)
    now = 0.0
    for _ in range(8):                  # prefill chunks: mixed steps
        if r.output_tokens:
            break
        eng.step(now)
        now += 0.01
    assert r.output_tokens and eng.stats["fused_iterations"] > 0
    assert eng.stats["decode_only_lanes"] == 0
    fused = eng.stats["fused_iterations"]
    eng.step(now)                       # decode only: 1 of 4 lanes live
    assert eng.stats["fused_iterations"] == fused
    assert eng.stats["decode_only_lanes"] == LANES
    assert eng.stats["decode_only_padded_lanes"] == LANES - 1
