"""Engine data plane: the share of pure-decode dispatches' lanes that
carried padding over the window (``decode_only_padded_lanes`` over
``decode_only_lanes``; mixed steps are ``padded_token_share``'s). None
where the engine does not count them."""


def read(run):
    d = run.stats_delta()
    lanes = d.get("decode_only_lanes", 0)
    if not lanes:
        return None
    return 100.0 * d["decode_only_padded_lanes"] / lanes
