"""Model: forward operations of every batch the window ran, at (batch, seq),
over the window times the chip's bf16 peak (moves ``served_rps``).  The
operations are the benchmark's own count (``bench.flops``)."""
from bench.flops import forward_flops


def read(run):
    if run.window_s <= 0 or not run.measured:
        return None
    ops = sum(
        len(v) * forward_flops(run.archs[m], b, run.seq) for (m, b), v in run.measured.items()
    )
    return 100.0 * ops / (run.window_s * run.peaks["flops_bf16"])
