"""Kernel: the Pallas paged-decode attention kernel's share of its
roofline. Least time is, per kernel call (one layer of one step's
decode lanes), the larger of FLOPs over peak and bytes over HBM
bandwidth, counted from the keys each lane's query reads in that layer
(``bench.work.decode_attention``); summed over layers and steps, it is
divided by the kernel's device time in the trace.

The kernel is the ``pallas_call`` of ``kernels/paged_attention.py``,
which has no name of its own: in a TPU trace its op is a custom call
named after the step program that holds it, returning the kernel's
three float32 partials (``%_decode_paged_impl.63 = (f32[4,5,32,3,64],
f32[4,5,32,3,1], f32[4,5,32,3,1]) custom-call(...)``); no other custom
call of the step returns a 5-d float32 tuple."""

from bench.trace import matching
from bench.work import decode_attention, least_time

PATTERNS = (r"^%\S+ = \(f32\[\d+,\d+,\d+,\d+,\d+\]\{.*\) custom-call\(",)


def read(run):
    if run.trace is None:
        return None
    t = sum(e - s for c in run.trace.chips
            for _, s, e in matching(c.ops, PATTERNS))
    calls = [st.dec_lens for st in run.steps if st.dec_lens]
    if t <= 0 or not calls:
        return None
    least = sum(least_time(*decode_attention(run.dims, layer, lens),
                           run.peaks)
                for lens in calls for layer in range(run.dims.layers))
    return 100.0 * least / t
