"""A kernel's share of its roofline over the traced chunk.

The least time the chip could take for a call is the larger of its
operations over the bf16 peak and its bytes over the HBM bandwidth
(``bench/kernels/<kernel>.py``).  The share is the sum of that over every
call the traced forwards made, over the kernel's summed device time in the
trace.  Where the trace holds no event of the kernel, or not one per call the
forwards should make, there is nothing sound to read and the share is None.
"""
from __future__ import annotations

from bench import manifest


def kernel_roofline(run, name: str):
    if run.trace is None or not run.traced_forwards:
        return None
    k = manifest.kernel(name, run.cell.bench_dir)
    seconds, events = run.trace.kernel(k.TRACE_NAME)
    least, calls = 0.0, 0
    for (m, b), n in run.traced_forwards.items():
        for shapes in k.calls(run.archs[m], b, run.seq):
            ops, nbytes = k.cost(**shapes)
            least += n * max(ops / run.peaks["flops_bf16"], nbytes / run.peaks["hbm_bytes_per_s"])
            calls += n
    if seconds <= 0 or events != calls:
        return None
    return 100.0 * least / seconds
