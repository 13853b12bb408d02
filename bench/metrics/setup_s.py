"""Seconds from the start of the process to the start of the window:
imports, weights, building and compiling (or loading) the engines'
programs, the cache warm-up and the ramp of traffic before the
window."""


def read(run):
    return run.setup_s
