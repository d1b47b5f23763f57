"""Dispatching wrappers around the Pallas kernels.

Models call these; on TPU (or with ``REPRO_FORCE_PALLAS=interpret``) they run
the Pallas kernels, otherwise the pure-jnp oracles in `ref`.  This keeps the
model code identical across CPU validation and TPU deployment.  Attention
whose whole sequence fits one block is the exception: there the kernel's
place goes to the same attention as XLA ops (`attention`).
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import functools
import os
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from . import ref


@functools.cache
def _mode() -> str:
    forced = os.environ.get("REPRO_FORCE_PALLAS", "")
    if forced in ("interpret", "tpu"):
        return forced
    return "tpu" if jax.default_backend() == "tpu" else "ref"


# (op, path) -> times traced.  Recorded at trace time, so a run can show
# which implementation each op took at the shapes it compiled.
TAKEN: collections.Counter = collections.Counter()


def _take(op: str, kernel_ok: bool) -> str:
    """The path ``op`` takes ("tpu", "interpret" or "ref"), counted in TAKEN."""
    mode = _mode() if kernel_ok else "ref"
    TAKEN[op, mode] += 1
    return mode


def _tileable(n: int, block: int) -> bool:
    """Whether a kernel can tile a length-``n`` axis with `fit_block`
    (blocks equal to the axis, or multiples of 8, are what TPU lowers)."""
    return n <= block or n % 8 == 0


@dataclass(frozen=True)
class MeshCtx:
    """Trace-time mesh context: lets ops shard_map themselves explicitly
    (attention is embarrassingly parallel over batch x heads, so wrapping it
    in shard_map guarantees ZERO collectives, where GSPMD propagation around
    a chunked scan can otherwise reshard the KV stream)."""

    mesh: object
    dp_axes: tuple[str, ...]
    model_axis: str
    dp_size: int
    model_size: int
    # True when the arch's attention heads divide the TP degree: the Megatron
    # constraint/row-parallel pattern only helps aligned models — forcing it
    # on unaligned ones (12 heads over TP=16) makes GSPMD reshard constantly.
    aligned: bool = True


_MESH_CTX: contextvars.ContextVar[MeshCtx | None] = contextvars.ContextVar(
    "repro_mesh_ctx", default=None
)


@contextlib.contextmanager
def mesh_context(ctx: MeshCtx | None):
    token = _MESH_CTX.set(ctx)
    try:
        yield
    finally:
        _MESH_CTX.reset(token)


def constrain_activations(x):
    """Pin the canonical residual-stream sharding P(dp, None, ..., None).

    Without this, GSPMD propagates downstream layouts (e.g. the MoE's
    256-way flat-token sharding) BACKWARD through residual adds into wide
    attention intermediates and materializes full-replica gathers.
    """
    ctx = _MESH_CTX.get()
    if ctx is None or not ctx.aligned:
        return x
    from jax.sharding import PartitionSpec as P

    dp = ctx.dp_axes if len(ctx.dp_axes) > 1 else ctx.dp_axes[0]
    spec = P(*([dp] + [None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, spec)


def constrain_hidden(x):
    """Pin Megatron-style hidden sharding P(dp, ..., 'model') on the last dim
    (FFN hidden, attention head outputs).  Forces GSPMD into the row-parallel
    partial-sum + all-reduce pattern instead of gathering the full hidden."""
    ctx = _MESH_CTX.get()
    if ctx is None or not ctx.aligned:
        return x
    from jax.sharding import PartitionSpec as P

    if x.shape[-1] % ctx.model_size != 0 or x.shape[0] % ctx.dp_size != 0:
        return x
    dp = ctx.dp_axes if len(ctx.dp_axes) > 1 else ctx.dp_axes[0]
    spec = P(*([dp] + [None] * (x.ndim - 2) + [ctx.model_axis]))
    return jax.lax.with_sharding_constraint(x, spec)


def row_parallel_dense(x, w):
    """Megatron row-parallel projection: x (..., f_sharded) @ w (f_sharded, d)
    -> psum over 'model'.  Explicit shard_map because the GSPMD cost model
    otherwise all-gathers the (much larger) hidden activation instead of
    all-reducing the small output."""
    ctx = _MESH_CTX.get()
    f = w.shape[-2]
    if (
        ctx is None
        or not ctx.aligned
        or f % ctx.model_size != 0
        or x.shape[0] % ctx.dp_size != 0
        or x.shape[-1] != f
    ):
        return x @ w.astype(x.dtype)
    from jax.sharding import PartitionSpec as P

    dp = ctx.dp_axes if len(ctx.dp_axes) > 1 else ctx.dp_axes[0]
    x_spec = P(*([dp] + [None] * (x.ndim - 2) + [ctx.model_axis]))
    w_spec = P(*([None] * (w.ndim - 2) + [ctx.model_axis, None]))
    out_spec = P(*([dp] + [None] * (x.ndim - 1)))

    def body(xx, ww):
        return jax.lax.psum(xx @ ww.astype(xx.dtype), ctx.model_axis)

    return jax.shard_map(
        body, mesh=ctx.mesh, in_specs=(x_spec, w_spec), out_specs=out_spec,
        check_vma=False,
    )(x, w)


def _shardable_attn(ctx: MeshCtx | None, q, k) -> bool:
    if ctx is None:
        return False
    B, _, Hq, _ = q.shape
    Hkv = k.shape[2]
    # MQA/low-kv archs replicate KV across model ranks inside the shard_map;
    # each rank's local query heads must still form whole KV groups
    kv_ok = Hkv % ctx.model_size == 0 or (
        Hq % ctx.model_size == 0 and (Hq // ctx.model_size) % Hkv == 0
    )
    return B % ctx.dp_size == 0 and Hq % ctx.model_size == 0 and kv_ok


def _sharded_attention(ctx: MeshCtx, q, k, v, *, causal, window, scale):
    """shard_map over (batch -> dp, heads -> model): fully local attention.

    When KV heads do not divide the model axis (MQA), KV is replicated across
    model ranks and each rank serves its local query-head group.
    """
    from jax.sharding import PartitionSpec as P

    dp = ctx.dp_axes if len(ctx.dp_axes) > 1 else ctx.dp_axes[0]
    q_spec = P(dp, None, ctx.model_axis, None)
    kv_sharded = k.shape[2] % ctx.model_size == 0
    kv_spec = q_spec if kv_sharded else P(dp, None, None, None)

    def body(qq, kk, vv):
        # with replicated KV the local query-head group size is Hq_loc / Hkv
        if kk.shape[1] >= 8192 and kk.shape[1] % 1024 == 0:
            return ref.attention_chunked(
                qq, kk, vv, causal=causal, window=window, scale=scale
            )
        return ref.attention(qq, kk, vv, causal=causal, window=window, scale=scale)

    fn = jax.shard_map(
        body,
        mesh=ctx.mesh,
        in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec,
        check_vma=False,
    )
    return fn(q, k, v)


def _xla_attention(q, k, v, *, causal, window, scale):
    """Attention as XLA ops, no less precise than the kernel: QK^T on the
    operands as they come, accumulated in f32 (bf16 products are exact
    there), the softmax in f32, and PV on f32 p and v in three bf16 passes
    (``Precision.HIGH``: p carried to ~16 bits; a TPU's default-precision f32
    dot would round it to bf16's 8)."""
    B, Sq, Hq, Dk = q.shape
    Hkv = k.shape[2]
    prec = jax.lax.Precision.HIGH
    scale = scale if scale is not None else Dk ** -0.5
    logits = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q.reshape(B, Sq, Hkv, Hq // Hkv, Dk), k,
        precision=prec, preferred_element_type=jnp.float32,
    ) * scale
    mask = ref._mask(Sq, k.shape[1], causal=causal, window=window)
    p = jax.nn.softmax(jnp.where(mask, logits, ref.NEG_INF), axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32), precision=prec)
    return out.reshape(B, Sq, Hq, -1).astype(q.dtype)


def attention(q, k, v, *, causal=True, window=None, scale=None, q_offset=0, kv_len=None):
    # Where one 128 block holds the whole sequence, the kernel's grid is one
    # small step per (batch row, head) over a KV axis of length 1, and the
    # same attention as XLA ops makes the faster forward (TPU v5e, seq 128:
    # smollm-360m b32 38.7 -> 20.5 ms, qwen1.5-4b b32 197.8 -> 190.6 ms).
    if kv_len is None and q.shape[1] == k.shape[1] <= 128 and _mode() != "ref":
        TAKEN["attention", "xla"] += 1
        return _xla_attention(q, k, v, causal=causal, window=window, scale=scale)
    mode = _take("attention", kv_len is None and _tileable(q.shape[1], 128))
    if mode != "ref":
        from .flash_attention import flash_attention

        return flash_attention(
            q,
            k,
            v,
            causal=causal,
            window=window,
            scale=scale,
            interpret=mode == "interpret",
        )
    ctx = _MESH_CTX.get()
    if kv_len is None and q_offset == 0 and _shardable_attn(ctx, q, k):
        return _sharded_attention(ctx, q, k, v, causal=causal, window=window, scale=scale)
    # Long sequences WITHOUT a mesh: chunked online-softmax (never materialize
    # S^2 logits).  Under GSPMD (ctx set but heads not shardable) the chunked
    # scan makes the partitioner replicate the KV stream per step — the plain
    # einsum form partitions far better there (see EXPERIMENTS.md SecPerf A.1).
    if (
        ctx is None
        and kv_len is None
        and q_offset == 0
        and k.shape[1] >= 8192
        and k.shape[1] % 1024 == 0
    ):
        return ref.attention_chunked(
            q, k, v, causal=causal, window=window, scale=scale
        )
    return ref.attention(
        q, k, v, causal=causal, window=window, scale=scale, q_offset=q_offset, kv_len=kv_len
    )


def mla_prefill_attention(q_nope, q_rope, k_nope, kr, v, *, scale):
    """MLA naive-form prefill attention with the head-concat INSIDE the
    shard_map boundary: q = [q_nope ; q_rope], k = [k_nope ; broadcast(kr)],
    then `attention`'s dispatch (the per-device body sees no mesh).

    Keeping the concatenation of the per-head (sharded) and shared-rope
    (replicated) halves inside per-device code stops GSPMD from gathering
    full-head tensors every layer.
    """
    B, S, H, dn = q_nope.shape
    dr = q_rope.shape[-1]

    def body(qn, qr, kn, krr, vv):
        k = jnp.concatenate(
            [kn, jnp.broadcast_to(krr[:, :, None], (*kn.shape[:3], dr))], -1
        )
        q = jnp.concatenate([qn, qr], -1)
        with mesh_context(None):
            return attention(q, k, vv, causal=True, scale=scale)

    ctx = _MESH_CTX.get()
    if ctx is not None and B % ctx.dp_size == 0 and H % ctx.model_size == 0:
        from jax.sharding import PartitionSpec as P

        dp = ctx.dp_axes if len(ctx.dp_axes) > 1 else ctx.dp_axes[0]
        hspec = P(dp, None, ctx.model_axis, None)
        fn = jax.shard_map(
            body,
            mesh=ctx.mesh,
            in_specs=(hspec, hspec, hspec, P(dp, None, None), hspec),
            out_specs=hspec,
            check_vma=False,
        )
        return fn(q_nope, q_rope, k_nope, kr, v)
    return body(q_nope, q_rope, k_nope, kr, v)


def expert_gmm(x, w, group_sizes, *, layer=None):
    """The experts' grouped matmul: rows of ``x`` (m, k) sorted by expert,
    ``group_sizes[e]`` of them for expert ``e``, times ``w[e]`` (k, n).

    With ``layer``, ``w`` is a stack (L, E, k, n) of every layer's experts and
    the product is with ``w[layer]``: the kernel reads that layer's experts
    in place, as groups ``layer * E + e`` of the stack seen as (L * E, k, n)
    with every other group empty."""
    mode = _take("expert_gmm", True)
    w = w.astype(x.dtype)  # no-op where the weights are stored in the compute dtype
    if mode != "ref":
        from .expert_gmm import gmm

        if layer is not None:
            L, E = w.shape[:2]
            w = w.reshape(L * E, *w.shape[2:])
            group_sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((L * E,), group_sizes.dtype), group_sizes, (layer * E,)
            )
        return gmm(x, w, group_sizes, interpret=mode == "interpret")
    if layer is not None:
        w = w[layer]
    return jax.lax.ragged_dot(x, w, group_sizes)


def decode_attention(q, k_cache, v_cache, lengths, *, window=None, scale=None):
    mode = _take("decode_attention", _tileable(k_cache.shape[1], 512))
    if mode != "ref":
        from .decode_attention import flash_decode

        return flash_decode(
            q,
            k_cache,
            v_cache,
            lengths,
            window=window,
            scale=scale,
            interpret=mode == "interpret",
        )
    return ref.decode_attention(q, k_cache, v_cache, lengths, window=window, scale=scale)


def rmsnorm(x, w, *, eps=1e-6, gemma=False):
    mode = _take("rmsnorm", True)
    if mode != "ref":
        from .rmsnorm import fused_rmsnorm

        return fused_rmsnorm(x, w, eps=eps, gemma=gemma, interpret=mode == "interpret")
    return ref.rmsnorm(x, w, eps=eps, gemma=gemma)


def selective_scan(x, dt, A, Bm, Cm, h0=None):
    mode = _take("selective_scan", x.shape[1] % 128 == 0)
    if mode != "ref":
        from .ssm_scan import chunked_selective_scan

        return chunked_selective_scan(x, dt, A, Bm, Cm, h0, interpret=mode == "interpret")
    return ref.selective_scan(x, dt, A, Bm, Cm, h0)


def mlstm(q, k, v, i_gate, f_gate, *, chunk=128):
    mode = _take("mlstm", q.shape[1] % chunk == 0)
    if mode != "ref":
        from .mlstm_chunk import chunked_mlstm

        return chunked_mlstm(
            q, k, v, i_gate, f_gate, chunk=chunk, interpret=mode == "interpret"
        )
    return ref.mlstm_chunked(q, k, v, i_gate, f_gate, chunk=chunk)
