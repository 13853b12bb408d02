"""Output tokens the harness saw inside the window, of any request,
over the window's length."""


def read(run):
    t0, t1 = run.window
    n = sum(1 for f in run.flights for t in f.token_times if t0 <= t < t1)
    return n / (t1 - t0)
