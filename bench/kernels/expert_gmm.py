"""expert_gmm (``repro.kernels.expert_gmm``): the experts' grouped matmul.

One call multiplies the ``rows`` (token, expert) rows of an expert layer,
sorted by expert, by their expert's (k, n) weight: three calls a layer, the
gate and up projections (k = d_model, n = d_ff_expert) and the down
projection (k = d_ff_expert, n = d_model).  Bytes are every expert weight the
call reads, the rows in and the rows out, each once.  At a seq-128 prefill
every expert is read: one token's 128 x top_k routes leave a given expert of
64 unchosen with probability (1 - 6/64) ** 128, about 3e-6.
"""
from __future__ import annotations

# the kernel's name in the device trace (the jitted wrapper's name)
TRACE_NAME = "expert_gmm"


def cost(*, rows: int, k: int, n: int, experts: int, dtype_bytes: int = 2) -> tuple[float, float]:
    ops = 2.0 * rows * k * n
    return ops, float((experts * k * n + rows * k + rows * n) * dtype_bytes)


def calls(arch: dict, batch: int, seq: int) -> list[dict]:
    if not arch.get("n_experts"):
        return []
    rows = batch * seq * arch["top_k"]
    d, fe, e = arch["d_model"], arch["d_ff_expert"], arch["n_experts"]
    up = dict(rows=rows, k=d, n=fe, experts=e)
    down = dict(rows=rows, k=fe, n=d, experts=e)
    return [up, up, down] * (arch["n_layers"] - arch["n_dense_layers"])
