"""Device: the share of the traced window in which chip ``i``
(``/device:TPU:<i>``, engine ``i``'s replica device) runs no operation
while engine ``i``'s ``preble.engine.step.<i>`` span is open and its
``preble.engine.fetch.<i>`` span is not: device idle time that the
engine's own host work holds. Mean over the chips that have an
engine; part of ``device_idle_share`` by construction."""

from bench import program_spans as ps
from bench.trace import gaps


def read(run):
    spans = ps.of(run)
    if spans is None or not run.trace.chips or run.trace.window_s <= 0:
        return None
    chips = {c.name: c for c in run.trace.chips}
    shares = []
    for inst, kinds in ps.engines(spans).items():
        chip = chips.get(f"/device:TPU:{inst}")
        if chip is None:
            continue
        host = ps.minus(kinds.get("step", []), kinds.get("fetch", []))
        idle = ps.overlap_s(gaps(chip, run.trace.lo, run.trace.hi), host)
        shares.append(idle / run.trace.window_s)
    return 100.0 * sum(shares) / len(shares) if shares else None
