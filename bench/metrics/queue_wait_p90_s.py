"""Local scheduler: 90th percentile of each measured request's wait
from its due time to its first step on an engine
(``Request.first_run_time``, stamped with the wall-clock ``now`` the
step was given); one never started counts with its wait by the end."""

from bench.stats import percentile


def read(run):
    return percentile([(f.request.first_run_time or run.end) - f.due
                       for f in run.measured], 0.90)
