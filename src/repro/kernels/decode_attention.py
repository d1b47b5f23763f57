"""Pallas TPU flash-decode: one query token against a long KV cache.

The decode step is memory-bound — the entire KV cache streams HBM -> VMEM
once.  Grid = (B, Hkv, S / BK) with the cache dimension innermost so the
(g, Dv) accumulator for the g = Hq/Hkv grouped queries stays in VMEM.  The
per-sequence valid length arrives via scalar prefetch (SMEM) and masks the
tail block; an optional sliding window masks the head blocks.

The kernel tiles a (B, Hkv, S, D) view of the (B, S, Hkv, D) cache so each
KV block is (BK, D) in its last two dimensions, which the TPU lowering
accepts for any Hkv (a (BK, 1, D) block of the native layout lowers only
when Hkv == 1).  For Hkv > 1 that view is a transpose XLA materializes.

Oracle: `repro.kernels.ref.decode_attention`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import fit_block

NEG_INF = -1e30


def _kernel(
    lengths_ref,  # scalar prefetch (B,) int32 in SMEM
    q_ref,  # (1, 1, g, Dk)
    k_ref,  # (1, 1, bk, Dk)
    v_ref,  # (1, 1, bk, Dv)
    o_ref,  # (1, 1, g, Dv)
    acc_ref,
    m_ref,
    l_ref,
    *,
    scale: float,
    window: int | None,
    bk: int,
    nk: int,
):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    length = lengths_ref[b]

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    k_start = ki * bk
    live = k_start < length
    if window is not None:
        live = jnp.logical_and(live, k_start + bk - 1 > length - 1 - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (g, Dk)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, Dk)
        v = v_ref[0, 0].astype(jnp.float32)  # (bk, Dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (g, bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < length
        if window is not None:
            mask &= kpos > length - 1 - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1)
        acc_ref[:] = acc_ref[:] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[:] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[:] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "scale", "block_k", "interpret")
)
def flash_decode(
    q: jax.Array,  # (B, Hq, Dk)
    k_cache: jax.Array,  # (B, S, Hkv, Dk)
    v_cache: jax.Array,  # (B, S, Hkv, Dv)
    lengths: jax.Array,  # (B,) int32
    *,
    window: int | None = None,
    scale: float | None = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, Dk = q.shape
    _, S, Hkv, _ = k_cache.shape
    Dv = v_cache.shape[-1]
    g = Hq // Hkv
    scale = scale if scale is not None else Dk ** -0.5
    bk = fit_block(S, block_k)
    nk = S // bk

    qr = q.reshape(B, Hkv, g, Dk)
    kt = k_cache.transpose(0, 2, 1, 3)  # (B, Hkv, S, Dk)
    vt = v_cache.transpose(0, 2, 1, 3)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, Dk), lambda b, h, ki, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, Dk), lambda b, h, ki, lens: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, Dv), lambda b, h, ki, lens: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, Dv), lambda b, h, ki, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, Dv), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window, bk=bk, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qr, kt, vt)
    return out.reshape(B, Hq, Dv)
