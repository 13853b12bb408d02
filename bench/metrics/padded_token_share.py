"""Engine data plane: the share of the mixed steps' token slots that
carried padding (``fused_padded_tokens`` over ``fused_iterations``
times lanes x chunk + lanes) over the window. Decode-only steps pad
too but the engine does not count them."""


def read(run):
    d = run.stats_delta()
    slots = d["fused_iterations"] * (run.lanes * run.chunk + run.lanes)
    return None if slots == 0 else 100.0 * d["fused_padded_tokens"] / slots
