"""Per-layer metric readers, one file each, found by the metric's name.

Each file defines ``read(run) -> float | None`` over the run record
(``bench.harness.Run``): the plan, the chunks' ``ServeResult``s, the
executor step times ``LiveServiceTime.measured`` of the window, the batch
totals of the loop's metrics registry and the reduced device trace.  A reader
that finds nothing to read returns None, and the metric is left out.
"""
