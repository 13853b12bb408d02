"""Local scheduler and radix tree: the share of prompt tokens served
from cached KV instead of prefilled, over the window, summed over the
engines (engine counters ``reused_tokens`` and ``prefilled_tokens``)."""


def read(run):
    d = run.stats_delta()
    total = d["reused_tokens"] + d["prefilled_tokens"]
    return None if total == 0 else 100.0 * d["reused_tokens"] / total
