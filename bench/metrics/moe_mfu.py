"""Model: forward operations of every batch the window ran of a model with
latent attention and routed experts, at (batch, seq), over the window times
the chip's bf16 peak (moves ``served_rps``).  The operations are the
benchmark's own count (``bench.flops_moe``): active experts only.  None for
a run whose modules are not all of that kind."""
from bench.flops_moe import forward_flops


def read(run):
    if run.window_s <= 0 or not run.measured:
        return None
    if not all(a.get("attn_kind") == "mla" and a.get("n_experts") for a in run.archs.values()):
        return None
    ops = sum(
        len(v) * forward_flops(run.archs[m], b, run.seq) for (m, b), v in run.measured.items()
    )
    return 100.0 * ops / (run.window_s * run.peaks["flops_bf16"])
