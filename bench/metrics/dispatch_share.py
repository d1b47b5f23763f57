"""Executor: the window's wall time spent in the program's ``dispatch`` spans,
the host enqueueing each compiled forward, from the metrics registry's
``dispatch_s`` rows in the traced run (moves ``served_rps``)."""
from bench.rows import window_share


def read(run):
    return window_share(run, "dispatch_s")
