"""Executor: the window's wall time spent inside executor calls, timed by
``LiveServiceTime`` around each forward; the rest is the loop's own host
time (moves ``served_rps``)."""


def read(run):
    busy = sum(sum(v) for v in run.measured.values())
    return 100.0 * busy / run.window_s if run.window_s > 0 else None
