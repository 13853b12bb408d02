"""Engine data plane: host milliseconds per dispatching engine step.
Mean, over the program's ``preble.engine.step.<i>`` spans that start in
the traced window and hold a ``preble.engine.dispatch.<i>`` span, of
the step's duration less its ``preble.engine.fetch.<i>`` spans (the
host blocked on the device). A step still open at the window's end
counts up to the end."""

from bench import program_spans as ps


def read(run):
    spans = ps.of(run)
    if spans is None:
        return None
    host = []
    for kinds in ps.engines(spans).values():
        steps = [s for s in kinds.get("step", []) if s[1] > run.trace.lo]
        for (_, lo, hi), disp, fetch in zip(
                steps, ps.nest(steps, kinds.get("dispatch", [])),
                ps.nest(steps, kinds.get("fetch", []))):
            if disp:
                host.append(hi - lo - sum(e - s for _, s, e in fetch))
    return 1e3 * sum(host) / len(host) if host else None
