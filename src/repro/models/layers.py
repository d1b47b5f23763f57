"""Shared decoder layers: norms, RoPE / M-RoPE, GQA + MLA attention, gated MLPs.

Everything is a pure function over parameter pytrees (plain dicts); no flax.
Attention math is delegated to `repro.kernels.ops` so the same model runs the
jnp oracle on CPU and the Pallas kernels on TPU.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig, LayerSpec
from ..kernels import ops

Params = dict[str, Any]


# --------------------------------------------------------------------- init
def _dense_init(key, d_in: int, d_out: int, dtype, *, bias: bool = False) -> Params:
    w = jax.random.normal(key, (d_in, d_out), jnp.float32) * (d_in ** -0.5)
    p = {"w": w.astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p: Params, x: jax.Array) -> jax.Array:
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def norm_init(cfg: ArchConfig, d: int, dtype) -> Params:
    if cfg.norm == "ln":
        return {"w": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}
    # gemma-style (1 + w) stores zeros
    w = jnp.zeros((d,), dtype) if cfg.gemma_norm else jnp.ones((d,), dtype)
    return {"w": w}


def apply_norm(cfg: ArchConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.norm == "ln":
        x32 = x.astype(jnp.float32)
        mu = x32.mean(-1, keepdims=True)
        var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + 1e-5)
        return (y * p["w"].astype(jnp.float32) + p["b"].astype(jnp.float32)).astype(x.dtype)
    return ops.rmsnorm(x, p["w"], gemma=cfg.gemma_norm)


# --------------------------------------------------------------------- RoPE
def _rope_angles(pos: jax.Array, dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """pos (..., S) -> cos/sin (..., S, dim/2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = pos.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(
    x: jax.Array,  # (B, S, H, D)
    positions: jax.Array,  # (B, S) or (3, B, S) for M-RoPE
    theta: float,
    mrope_sections: tuple[int, ...] | None = None,
) -> jax.Array:
    D = x.shape[-1]
    if mrope_sections is None:
        cos, sin = _rope_angles(positions, D, theta)  # (B, S, D/2)
    else:
        # Qwen2-VL M-RoPE: the D/2 rotary frequencies are split into
        # (temporal, height, width) sections, each driven by its own 1-D
        # position stream.  Text tokens carry identical t/h/w positions, so
        # M-RoPE degenerates to 1-D RoPE for them.
        assert positions.ndim == 3 and sum(mrope_sections) == D // 2
        cos_full, sin_full = _rope_angles(positions, D, theta)  # (3, B, S, D/2)
        chunks_c, chunks_s = [], []
        off = 0
        for i, sec in enumerate(mrope_sections):
            chunks_c.append(cos_full[i, ..., off : off + sec])
            chunks_s.append(sin_full[i, ..., off : off + sec])
            off += sec
        cos = jnp.concatenate(chunks_c, -1)
        sin = jnp.concatenate(chunks_s, -1)
    cos = cos[:, :, None, :]  # (B, S, 1, D/2)
    sin = sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- attention
def attn_init(key, cfg: ArchConfig, dtype) -> Params:
    d, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hdim
    ks = jax.random.split(key, 6)
    p = {
        "q": _dense_init(ks[0], d, H * Dh, dtype, bias=cfg.qkv_bias),
        "k": _dense_init(ks[1], d, Hkv * Dh, dtype, bias=cfg.qkv_bias),
        "v": _dense_init(ks[2], d, Hkv * Dh, dtype, bias=cfg.qkv_bias),
        "o": _dense_init(ks[3], H * Dh, d, dtype),
    }
    if cfg.qk_norm:
        p["qn"] = {"w": jnp.ones((Dh,), dtype)}
        p["kn"] = {"w": jnp.ones((Dh,), dtype)}
    return p


def _qk_norm(cfg: ArchConfig, p: Params, q: jax.Array, k: jax.Array):
    if not cfg.qk_norm:
        return q, k
    return (
        ops.rmsnorm(q, p["qn"]["w"], gemma=cfg.gemma_norm),
        ops.rmsnorm(k, p["kn"]["w"], gemma=cfg.gemma_norm),
    )


def attn_cache_init(cfg: ArchConfig, spec: LayerSpec, batch: int, max_seq: int, dtype):
    size = min(max_seq, spec.window) if spec.window else max_seq
    Hkv, Dh = cfg.n_kv_heads, cfg.hdim
    return {
        "k": jnp.zeros((batch, size, Hkv, Dh), dtype),
        "v": jnp.zeros((batch, size, Hkv, Dh), dtype),
    }


def attn_forward(
    p: Params,
    cfg: ArchConfig,
    spec: LayerSpec,
    x: jax.Array,  # (B, S, d)
    positions: jax.Array,
    *,
    cache: Params | None = None,
    idx: jax.Array | None = None,  # scalar cache fill level (decode)
) -> tuple[jax.Array, Params | None]:
    B, S, _ = x.shape
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.hdim
    theta = spec.rope_theta or cfg.rope_theta
    q = dense(p["q"], x).reshape(B, S, H, Dh)
    k = dense(p["k"], x).reshape(B, S, Hkv, Dh)
    v = dense(p["v"], x).reshape(B, S, Hkv, Dh)
    q, k = _qk_norm(cfg, p, q, k)
    q = apply_rope(q, positions, theta, cfg.mrope_sections)
    k = apply_rope(k, positions, theta, cfg.mrope_sections)

    if cache is None:  # train / prefill without cache
        out = ops.attention(q, k, v, causal=True, window=spec.window)
        new_cache = None
    elif S > 1:  # prefill into cache
        size = cache["k"].shape[1]
        k_in, v_in = k[:, -size:], v[:, -size:]
        if spec.window and S > size:
            # ring buffer: absolute position p lives in slot p % size
            k_in = jnp.roll(k_in, S % size, axis=1)
            v_in = jnp.roll(v_in, S % size, axis=1)
        kc = jax.lax.dynamic_update_slice(cache["k"], k_in, (0, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(cache["v"], v_in, (0, 0, 0, 0))
        out = ops.attention(q, k, v, causal=True, window=spec.window)
        new_cache = {"k": kc, "v": vc}
    else:  # single-token decode
        size = cache["k"].shape[1]
        write = idx % size if spec.window else jnp.minimum(idx, size - 1)
        kc = jax.lax.dynamic_update_slice(cache["k"], k, (0, write, 0, 0))
        vc = jax.lax.dynamic_update_slice(cache["v"], v, (0, write, 0, 0))
        lengths = jnp.full((B,), jnp.minimum(idx + 1, size), jnp.int32)
        ring = spec.window is not None
        out = ops.decode_attention(
            q[:, 0],
            kc,
            vc,
            lengths,
            window=None if ring else spec.window,
        )[:, None]
        new_cache = {"k": kc, "v": vc}
    y = ops.row_parallel_dense(out.reshape(B, S, H * Dh), p["o"]["w"])
    return y, new_cache


# ---------------------------------------------------------------- MLA (deepseek)
def mla_init(key, cfg: ArchConfig, dtype) -> Params:
    d, H = cfg.d_model, cfg.n_heads
    dq, dc, dr = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.rope_head_dim
    dn, dv = cfg.hdim, cfg.vdim
    ks = jax.random.split(key, 8)
    p: Params = {
        "kv_a": _dense_init(ks[2], d, dc + dr, dtype),  # down-proj + shared k_rope
        "kv_norm": {"w": jnp.ones((dc,), dtype)},
        "k_b": _dense_init(ks[3], dc, H * dn, dtype),  # W_UK
        "v_b": _dense_init(ks[4], dc, H * dv, dtype),  # W_UV
        "o": _dense_init(ks[5], H * dv, d, dtype),
    }
    if dq:
        p["q_a"] = _dense_init(ks[0], d, dq, dtype)
        p["q_norm"] = {"w": jnp.ones((dq,), dtype)}
        p["q_b"] = _dense_init(ks[1], dq, H * (dn + dr), dtype)
    else:
        p["q_b"] = _dense_init(ks[1], d, H * (dn + dr), dtype)
    return p


def mla_cache_init(cfg: ArchConfig, batch: int, max_seq: int, dtype):
    return {
        "ckv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype),
        "kr": jnp.zeros((batch, max_seq, cfg.rope_head_dim), dtype),
    }


def _mla_q(p: Params, cfg: ArchConfig, x, positions):
    B, S, _ = x.shape
    H, dn, dr = cfg.n_heads, cfg.hdim, cfg.rope_head_dim
    if "q_a" in p:
        qa = ops.rmsnorm(dense(p["q_a"], x), p["q_norm"]["w"])
        q = dense(p["q_b"], qa)
    else:
        q = dense(p["q_b"], x)
    q = q.reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_forward(
    p: Params,
    cfg: ArchConfig,
    x: jax.Array,
    positions: jax.Array,
    *,
    cache: Params | None = None,
    idx: jax.Array | None = None,
) -> tuple[jax.Array, Params | None]:
    """Multi-head Latent Attention.  Prefill runs the naive (expanded) form;
    decode runs the absorbed form against the compressed cache — a single
    MQA-style flash-decode with K = [c_kv ; k_rope], V = c_kv.  Its ops carry
    the ``mla`` name scope."""
    with jax.named_scope("mla"):
        return _mla(p, cfg, x, positions, cache, idx)


def _mla(p, cfg, x, positions, cache, idx):
    B, S, _ = x.shape
    H, dn, dv = cfg.n_heads, cfg.hdim, cfg.vdim
    dc, dr = cfg.kv_lora_rank, cfg.rope_head_dim
    scale = (dn + dr) ** -0.5
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    kv = dense(p["kv_a"], x)
    ckv = ops.rmsnorm(kv[..., :dc], p["kv_norm"]["w"])
    kr = apply_rope(kv[..., dc:][:, :, None, :], positions, cfg.rope_theta)[:, :, 0]

    if S > 1 or cache is None:
        # naive form: expand per-head K/V from the latent; the head-concat of
        # the rope halves happens inside the (possibly shard_mapped) op
        k_nope = dense(p["k_b"], ckv).reshape(B, S, H, dn)
        vfull = dense(p["v_b"], ckv).reshape(B, S, H, dv)
        out = ops.mla_prefill_attention(q_nope, q_rope, k_nope, kr, vfull, scale=scale)
        new_cache = None
        if cache is not None:
            size = cache["ckv"].shape[1]
            new_cache = {
                "ckv": jax.lax.dynamic_update_slice(cache["ckv"], ckv[:, -size:], (0, 0, 0)),
                "kr": jax.lax.dynamic_update_slice(cache["kr"], kr[:, -size:], (0, 0, 0)),
            }
    else:
        # absorbed decode: q' = q_nope @ W_UK  ->  (B, H, dc)
        wk = p["k_b"]["w"].astype(jnp.float32).reshape(dc, H, dn)
        q_abs = jnp.einsum("bhd,chd->bhc", q_nope[:, 0].astype(jnp.float32), wk)
        q_cat = jnp.concatenate([q_abs.astype(x.dtype), jnp.broadcast_to(
            q_rope[:, 0], (B, H, dr))], -1)  # (B, H, dc + dr)
        ckv_c = jax.lax.dynamic_update_slice(cache["ckv"], ckv, (0, idx, 0))
        kr_c = jax.lax.dynamic_update_slice(cache["kr"], kr, (0, idx, 0))
        kcat = jnp.concatenate([ckv_c, kr_c], -1)[:, :, None, :]  # MQA: 1 kv head
        lengths = jnp.full((B,), idx + 1, jnp.int32)
        ctx = ops.decode_attention(
            q_cat, kcat, ckv_c[:, :, None, :], lengths, scale=scale
        )  # (B, H, dc)
        wv = p["v_b"]["w"].astype(jnp.float32).reshape(dc, H, dv)
        out = jnp.einsum("bhc,chd->bhd", ctx.astype(jnp.float32), wv).astype(x.dtype)
        out = out[:, None]  # (B, 1, H, dv)
        new_cache = {"ckv": ckv_c, "kr": kr_c}
    y = ops.row_parallel_dense(out.reshape(B, S, H * dv), p["o"]["w"])
    return y, new_cache


# --------------------------------------------------------------------- MLP
def mlp_init(key, d: int, d_ff: int, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w1": _dense_init(k1, d, d_ff, dtype),  # gate
        "w3": _dense_init(k2, d, d_ff, dtype),  # up
        "w2": _dense_init(k3, d_ff, d, dtype),  # down
    }


def mlp_forward(p: Params, x: jax.Array, act: str) -> jax.Array:
    g = dense(p["w1"], x)
    g = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)
    h = g * dense(p["w3"], x)
    return ops.row_parallel_dense(h, p["w2"]["w"])


# --------------------------------------------------------------- embeddings
def embed_init(key, cfg: ArchConfig, dtype) -> Params:
    w = jax.random.normal(key, (cfg.vocab_size, cfg.d_model), jnp.float32) * 0.02
    return {"w": w.astype(dtype)}


def embed(p: Params, cfg: ArchConfig, tokens: jax.Array, compute_dtype) -> jax.Array:
    x = p["w"].astype(compute_dtype)[tokens]
    if cfg.gemma_norm:
        x = x * math.sqrt(cfg.d_model)
    return x
