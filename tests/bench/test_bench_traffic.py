"""The benchmark's copy of the toolbench generator against the Preble
paper's Table 1, and the stratified sampling that gives every seed the
same work."""

import json

import numpy as np
import pytest

from bench import traffic
from bench.seeds import jax_key, numpy_rng


@pytest.fixture(scope="module")
def mix():
    return traffic.load_mix("toolbench")


def _shared_share(ps, system_len):
    """Mean share of each prompt that another prompt also starts with:
    the system prompt, and the segment when another prompt has it."""
    seen = np.bincount([p.segment for p in ps])
    out = []
    for p in ps:
        shared = system_len
        if seen[p.segment] > 1:
            # the segment's tokens: the same in every prompt that has it
            q = next(q for q in ps if q.segment == p.segment and q is not p)
            n = 0
            for a, b in zip(p.tokens, q.tokens):
                if a != b:
                    break
                n += 1
            shared = n
        out.append(shared / len(p.tokens))
    return float(np.mean(out))


def test_toolbench_lengths_follow_table_1(mix):
    rng = numpy_rng(7)
    ps = traffic.Layout(mix, 49152, rng).draw(400, rng, rng)
    st = traffic.length_stats(ps)
    # Table 1: prompt 1835 +- 742, output 43 +- 16, 85% shared
    assert st["prompt_mean"] == pytest.approx(1835, rel=0.08)
    assert st["output_mean"] == pytest.approx(43, abs=1.5)
    assert st["output_std"] == pytest.approx(16, abs=2)
    assert _shared_share(ps, mix["system_len"]) > 0.8
    assert max(len(p.tokens) + p.max_new_tokens for p in ps) <= 3546


def test_every_seed_replays_the_same_schedule(mix):
    kw = dict(rate=1.0, blocks=(15, 30, 15), vocab=1000, max_context=4096)
    a = traffic.stream(mix, seed=1, **kw)
    b = traffic.stream(mix, seed=2 ** 33 + 5, **kw)
    assert len(a.timed) == len(b.timed) == 60
    # the window block holds exactly 30 arrivals
    assert np.sum((a.due >= 15) & (a.due < 45)) == 30
    assert np.all(np.diff(a.due) > 0)
    assert np.array_equal(a.due, b.due)
    shape = lambda s: [(len(p.tokens), p.max_new_tokens, p.segment)
                       for p in s.fill + s.timed]
    assert shape(a) == shape(b)
    assert [p.tokens for p in a.timed] != [p.tokens for p in b.timed]


def test_same_seed_same_stream(mix):
    kw = dict(rate=2.0, blocks=(2, 5, 3), vocab=500, max_context=4096)
    a = traffic.stream(mix, seed=2 ** 31 + 11, **kw)
    b = traffic.stream(mix, seed=2 ** 31 + 11, **kw)
    assert [p.tokens for p in a.fill + a.timed] == [
        p.tokens for p in b.fill + b.timed]
    assert np.array_equal(a.due, b.due)


def test_poisson_gaps_have_the_rate_as_mean():
    g = traffic.gaps(2.0, 1000, numpy_rng(0))
    assert g.mean() == pytest.approx(0.5, rel=0.01)
    # exponential: the median gap is ln 2 of the mean
    assert np.median(g) == pytest.approx(0.5 * np.log(2), rel=0.01)


def test_other_arrival_processes_are_refused(tmp_path, monkeypatch):
    mix = dict(traffic.load_mix("toolbench"),
               arrivals={"process": "lognormal", "sigma": 2.2})
    (tmp_path / "bursty.json").write_text(json.dumps(mix))
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", tmp_path)
    with pytest.raises(ValueError):
        traffic.load_mix("bursty")


def test_unshared_mix_has_no_segment(mix):
    plain = dict(mix, segments=dict(mix["segments"], count=0))
    s = traffic.stream(plain, rate=1.0, blocks=(20,), vocab=1000,
                       max_context=4096, seed=3)
    assert s.fill == [] and len(s.timed) == 20
    assert {p.segment for p in s.timed} == {-1}
    n = plain["system_len"]
    assert all(p.tokens[:n] == s.timed[0].tokens[:n] for p in s.timed)
    assert max(len(p.tokens) for p in s.timed) <= (
        n + plain["unique_len"]["max"])


def test_zipf_ranks_follow_the_weights():
    r = traffic.stratified_ranks(64, 1.1, 1000, numpy_rng(3))
    counts = np.bincount(r, minlength=64)
    w = 1.0 / np.arange(1, 65) ** 1.1
    assert np.abs(counts - 1000 * w / w.sum()).max() <= 1


def test_normal_quantiles():
    # 5 lengths at the quantiles 0.1, 0.3, 0.5, 0.7, 0.9 of N(100, 10)
    spec = {"mean": 100, "std": 10, "min": 0, "max": 1000}
    v = np.sort(traffic.stratified_lengths(spec, 5, numpy_rng(0)))
    assert v.tolist() == [87, 95, 100, 105, 113]
    spec["min"], spec["max"] = 90, 110
    v = np.sort(traffic.stratified_lengths(spec, 5, numpy_rng(0)))
    assert v.tolist() == [90, 95, 100, 105, 110]


def test_request_past_max_context_is_refused(mix):
    with pytest.raises(ValueError):
        traffic.stream(mix, rate=1.0, blocks=(5,), vocab=100,
                       max_context=1024, seed=0)


def test_large_seeds_give_keys():
    assert jax_key(2 ** 31 + 7).shape == (2,)
    assert not np.array_equal(jax_key(5), jax_key(5 + 2 ** 32))
