"""99th percentile of every gap between consecutive output tokens of
the measured requests, milliseconds, on the harness's clock."""

from bench.stats import percentile


def read(run):
    gaps = [b - a for f in run.measured
            for a, b in zip(f.token_times, f.token_times[1:])]
    p = percentile(gaps, 0.99)
    return None if p is None else p * 1e3
