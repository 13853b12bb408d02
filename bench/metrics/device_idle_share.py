"""Device: the share of the traced window in which no operation ran,
mean over the cell's chips."""

from bench.trace import idle_share


def read(run):
    if run.trace is None or not run.trace.chips:
        return None
    return 100.0 * idle_share(run.trace)
