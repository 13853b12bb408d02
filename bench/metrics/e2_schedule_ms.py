"""Global scheduler E2: milliseconds per routing decision, the mean
duration of the program's ``preble.e2.schedule`` spans (one
``GlobalScheduler.schedule`` each) that start in the traced window."""

from bench import program_spans as ps


def read(run):
    spans = ps.of(run)
    if spans is None:
        return None
    d = [e - s for n, s, e in spans
         if n == ps.E2_SCHEDULE and s > run.trace.lo]
    return 1e3 * sum(d) / len(d) if d else None
