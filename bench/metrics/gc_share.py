"""Serving loop: the window's wall time spent in garbage collections, from the
metrics registry's ``gc_s`` on its ``(host)`` rows in the traced run (moves
``served_rps``)."""
from bench.rows import window_share


def read(run):
    return window_share(run, "gc_s")
