"""Serving loop: mean collection wait of a real member, batch close minus the
member's ready time in the loop's clock, from the metrics registry's
``collect_s`` / ``waited`` rows in the traced run (moves ``slo_attainment``)."""
from bench.rows import mean_ms


def read(run):
    return mean_ms(run, "collect_s")
