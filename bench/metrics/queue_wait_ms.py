"""Serving loop: mean queueing wait of a real member, batch start minus batch
close in the loop's clock, from the metrics registry's ``queue_s`` /
``waited`` rows in the traced run (moves ``slo_attainment``)."""
from bench.rows import mean_ms


def read(run):
    return mean_ms(run, "queue_s")
