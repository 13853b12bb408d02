"""Device: model FLOP utilisation of the step programs. Useful FLOPs
(every prefill and decode token through the layers, attention over its
true causal context, the head on emitted tokens only;
``bench.work``) over the device time of the mixed and decode step
programs times the chip's peak bf16 FLOP/s, summed over the chips."""

from bench.metrics import decode_step_ms, mixed_step_ms
from bench.trace import matching
from bench.work import head_flops, step_flops

PATTERNS = mixed_step_ms.PATTERNS + decode_step_ms.PATTERNS


def read(run):
    if run.trace is None or not run.steps:
        return None
    t = sum(e - s for c in run.trace.chips
            for _, s, e in matching(c.modules, PATTERNS))
    if t <= 0:
        return None
    flops = sum(step_flops(run.dims, st) for st in run.steps)
    flops += head_flops(run.dims, run.emitted_traced)
    return 100.0 * flops / (t * run.peaks["bf16_flops_per_s"])
