"""Median request latency, seconds: from each measured request's due
time to the harness seeing its last token; an unfinished request counts
with the wait it had by the end of the run."""

from bench.stats import percentile


def read(run):
    return percentile([(f.done if f.done is not None else run.end) - f.due
                       for f in run.measured], 0.50)
