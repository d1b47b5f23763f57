"""Executor: the window's wall time spent in the program's ``sync`` spans,
waiting in ``block_until_ready`` for each forward's logits, from the metrics
registry's ``sync_s`` rows in the traced run (moves ``served_rps``)."""
from bench.rows import window_share


def read(run):
    return window_share(run, "sync_s")
