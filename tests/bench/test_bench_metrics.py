"""Percentile and window arithmetic of the metric readers, on
hand-made runs: censored requests, tokens counted by the window they
fall in, counter deltas summed over engines."""

from types import SimpleNamespace as NS

import pytest

from bench.harness import Flight
from bench.metrics import (arrival_lag_p99_ms, itl_p99_ms, latency_p50_s,
                           output_tokens_per_s, padded_token_share,
                           prompt_cached_share, queue_wait_p90_s,
                           ttft_p50_s)
from bench.run import Run
from bench.stats import percentile
from bench.traffic import Prompt


def test_percentile_is_the_sorted_index():
    v = list(range(10, 0, -1))           # 10 values, unsorted
    assert percentile(v, 0.9) == 10      # v[min(int(9.0), 9)]
    assert percentile(v, 0.5) == 6
    assert percentile(v, 0.0) == 1
    assert percentile([3.0], 0.99) == 3.0
    assert percentile([], 0.5) is None


def _flight(due, submit, tokens, done=None, first_run=0.0, failed=False):
    f = Flight(Prompt((1, 2, 3), len(tokens), 0), due)
    f.submit, f.token_times, f.done, f.failed = submit, tokens, done, failed
    f.request = NS(first_run_time=first_run, output_tokens=[0] * len(tokens))
    return f


def _run(flights, measured, window=(10.0, 20.0), end=30.0, **kw):
    stats = kw.pop("stats", ({0: {}}, {0: {}}))
    return Run(window, end, flights, measured, 5.0, stats[0], stats[1],
               kw.pop("lanes", 4), kw.pop("chunk", 512), None, {}, **kw)


def test_latency_tails_count_unfinished_requests_as_censored():
    done = [_flight(10 + i, 10 + i, [11 + i, 11.5 + i], done=11.5 + i,
                    first_run=10.5 + i) for i in range(9)]
    stuck = _flight(19.0, 19.2, [], None)       # nothing by the end (30)
    r = _run(done + [stuck], done + [stuck])
    # ttft: nine of 1.0 s and one censored at 30 - 19 = 11 s
    assert ttft_p50_s.read(r) == pytest.approx(1.0)
    assert latency_p50_s.read(r) == pytest.approx(1.5)
    assert queue_wait_p90_s.read(r) == pytest.approx(11.0)
    # six of ten stuck, censored at 30 - due: 11, 12, ..., 16 s; the
    # median (sorted index 5) is the second of them
    many = [_flight(19.0 - k, 19.2 - k, [], None) for k in range(6)]
    r = _run(done[:4] + many, done[:4] + many)
    assert ttft_p50_s.read(r) == pytest.approx(12.0)
    assert latency_p50_s.read(r) == pytest.approx(12.0)
    r = _run(done, done)
    assert ttft_p50_s.read(r) == pytest.approx(1.0)
    assert latency_p50_s.read(r) == pytest.approx(1.5)
    assert queue_wait_p90_s.read(r) == pytest.approx(0.5)


def test_itl_is_every_gap_of_measured_requests():
    a = _flight(10, 10, [11.0, 11.1, 11.3], done=11.3)
    b = _flight(12, 12, [12.5, 13.5], done=13.5)
    unmeasured = _flight(5, 5, [6.0, 60.0], done=60.0)
    r = _run([a, b, unmeasured], [a, b])
    # gaps 0.1, 0.2, 1.0: p99 of three is the largest
    assert itl_p99_ms.read(r) == pytest.approx(1000.0)


def test_tokens_per_second_counts_tokens_inside_the_window():
    warm = _flight(5, 5, [9.0, 10.0, 10.5], done=10.5)      # 2 inside
    late = _flight(19, 19, [19.5, 20.0, 21.0], done=21.0)   # 1 inside
    r = _run([warm, late], [late])
    assert output_tokens_per_s.read(r) == pytest.approx(3 / 10)


def test_arrival_lag():
    fs = [_flight(10 + i, 10 + i + 0.001 * i, [11 + i], done=11 + i)
          for i in range(5)]
    assert arrival_lag_p99_ms.read(_run(fs, fs)) == pytest.approx(4.0)


def test_counter_shares_are_window_deltas_summed_over_engines():
    s0 = {0: {"reused_tokens": 100, "prefilled_tokens": 50,
              "fused_padded_tokens": 10, "fused_iterations": 1},
          1: {"reused_tokens": 0, "prefilled_tokens": 0,
              "fused_padded_tokens": 0, "fused_iterations": 0}}
    s1 = {0: {"reused_tokens": 400, "prefilled_tokens": 150,
              "fused_padded_tokens": 1010, "fused_iterations": 3},
          1: {"reused_tokens": 300, "prefilled_tokens": 100,
              "fused_padded_tokens": 1000, "fused_iterations": 2}}
    r = _run([], [], stats=(s0, s1), lanes=4, chunk=100)
    # reused 600, prefilled 200
    assert prompt_cached_share.read(r) == pytest.approx(75.0)
    # padded 2000 of 4 iterations x (4 x 100 + 4) slots
    assert padded_token_share.read(r) == pytest.approx(100 * 2000 / 1616)


def test_no_mixed_step_reads_nothing():
    s = {0: {"fused_padded_tokens": 0, "fused_iterations": 0,
             "reused_tokens": 0, "prefilled_tokens": 0}}
    r = _run([], [], stats=(s, s))
    assert padded_token_share.read(r) is None
    assert prompt_cached_share.read(r) is None

