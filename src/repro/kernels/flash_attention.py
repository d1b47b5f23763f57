"""Pallas TPU flash-attention (prefill/train): blocked online-softmax GQA.

Tiling: grid = (B * Hq, Sq / BQ, Sk / BK); the KV dimension is the innermost
(sequential / "arbitrary") grid axis so the (BQ, Dv) f32 accumulator, the
running max and the running denominator live in VMEM scratch across KV steps.
Q/K/V tiles are multiples of 128 on the lane dimension for the MXU; causal and
sliding-window masking skip fully-masked KV blocks via pl.when.

Oracle: `repro.kernels.ref.attention`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def fit_block(n: int, block: int) -> int:
    """The block that tiles a length-``n`` axis: ``n`` itself when it is at
    most ``block``, else the largest halving of ``block`` dividing ``n``.
    The TPU lowering takes a block equal to the whole axis or a multiple of
    8, so ``n <= block or n % 8 == 0`` is what a caller must ensure."""
    b = min(block, n)
    while n % b:
        b //= 2
    return b


def _kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    scale: float,
    causal: bool,
    window: int | None,
    bq: int,
    bk: int,
    nk: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = qi * bq
    k_start = ki * bk
    # block-level skip: fully-masked KV blocks do no work
    live = True
    if causal:
        live = k_start <= q_start + bq - 1
    if window is not None:
        live = jnp.logical_and(live, k_start + bk - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (BQ, D)
        k = k_ref[0].astype(jnp.float32)  # (BK, D)
        v = v_ref[0].astype(jnp.float32)  # (BK, Dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (BQ, BK)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
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
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (B, Sq, Hq, Dk)
    k: jax.Array,  # (B, Sk, Hkv, Dk)
    v: jax.Array,  # (B, Sk, Hkv, Dv)
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, Sq, Hq, Dk = q.shape
    _, Sk, Hkv, _ = k.shape
    Dv = v.shape[-1]
    g = Hq // Hkv
    scale = scale if scale is not None else Dk ** -0.5
    bq = fit_block(Sq, block_q)
    bk = fit_block(Sk, block_k)
    nk = Sk // bk

    qr = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, Dk)
    kr = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, Dk)
    vr = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, Dv)

    def kv_index(bh, qi, ki):
        return ((bh // Hq) * Hkv + (bh % Hq) // g, ki, 0)

    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, causal=causal, window=window, bq=bq, bk=bk, nk=nk
        ),
        grid=(B * Hq, Sq // bq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, Dk), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, Dk), kv_index),
            pl.BlockSpec((1, bk, Dv), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, Dv), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, Dv), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, Hq, Sq, Dv).transpose(0, 2, 1, 3)
