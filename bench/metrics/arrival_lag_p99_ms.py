"""Load generator: 99th percentile of how late each measured request
was submitted after its due time, milliseconds (the harness steps the
cluster between submissions, so a long step delays the next one)."""

from bench.stats import percentile


def read(run):
    p = percentile([f.submit - f.due for f in run.measured], 0.99)
    return None if p is None else p * 1e3
