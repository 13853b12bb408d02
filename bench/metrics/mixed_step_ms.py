"""Model dispatch: device milliseconds per execution of the mixed
prefill+decode step program (``zoo.mixed_paged``), from the trace's
module events, mean over executions on every chip."""

from bench.trace import matching

PATTERNS = (r"_mixed_paged_impl",)


def read(run):
    if run.trace is None:
        return None
    evs = [e for c in run.trace.chips for e in matching(c.modules, PATTERNS)]
    if not evs:
        return None
    return 1e3 * sum(e - s for _, s, e in evs) / len(evs)
