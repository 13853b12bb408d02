"""Median time to first token, seconds: from each measured request's
due time to the harness seeing its first token; a request with no token
by the end of the run counts with the wait it had."""

from bench.stats import percentile


def read(run):
    return percentile([(f.token_times[0] if f.token_times else run.end)
                       - f.due for f in run.measured], 0.50)
