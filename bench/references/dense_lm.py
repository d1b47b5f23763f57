"""Plain float32 reference of a dense decoder LM (Llama / Qwen1.5 family).

It follows the published description of these models and imports nothing of
the program: token embedding; per layer, RMSNorm, q/k/v projections (with
bias where the configuration has ``qkv_bias``), rotary embedding in the
rotate-half layout, causal softmax attention with each group of query heads
sharing one key/value head, output projection and residual; RMSNorm, gated
SiLU MLP and residual; a final RMSNorm and the unembedding (tied to the
embedding where ``tie_embeddings``).  Every matrix multiplication runs at
``Precision.HIGHEST`` so that a TPU computes it in float32.

The weights are those the benchmark made (``bench.weights``), in the layer
layout of a parameter tree: ``segments`` is a list of patterns, each a tuple
of block dicts whose leaves are stacked along a leading axis when the pattern
repeats.  The reference reads that layout and nothing else.

``quant`` replaces each weight matmul's two operands by their values in a
lower precision: the control of the comparison (see ``fp8``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def fp8(x: jax.Array) -> jax.Array:
    """``x`` rounded to float8 e4m3 with one scale per tensor (the control)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(x, w, quant):
    w = w.astype(F32)
    if quant is not None:
        x, w = quant(x), quant(w)
    return jnp.matmul(x, w, precision=HI)


def _dense(p, x, quant):
    y = _mm(x, p["w"], quant)
    if "b" in p:
        y = y + p["b"].astype(F32)
    return y


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _rope(x, theta):
    """Rotate-half rotary embedding of ``x`` (B, S, H, D) at positions 0..S-1."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv  # (S, D/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v):
    B, S, H, D = q.shape
    g = H // k.shape[2]
    k = jnp.repeat(k, g, axis=2)  # query head h reads key/value head h // g
    v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * (D ** -0.5)
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)


def _layer(p, x, arch, eps, quant):
    B, S, _ = x.shape
    H, Hkv = arch["n_heads"], arch["n_kv_heads"]
    D = arch.get("head_dim") or arch["d_model"] // H
    h = _rms(x, p["mix_norm"]["w"], eps)
    a = p["mix"]
    q = _dense(a["q"], h, quant).reshape(B, S, H, D)
    k = _dense(a["k"], h, quant).reshape(B, S, Hkv, D)
    v = _dense(a["v"], h, quant).reshape(B, S, Hkv, D)
    q, k = _rope(q, arch["rope_theta"]), _rope(k, arch["rope_theta"])
    o = _attention(q, k, v).reshape(B, S, H * D)
    x = x + _mm(o, a["o"]["w"], quant)
    h = _rms(x, p["ffn_norm"]["w"], eps)
    f = p["ffn"]
    gate = jax.nn.silu(_mm(h, f["w1"]["w"], quant))
    x = x + _mm(gate * _mm(h, f["w3"]["w"], quant), f["w2"]["w"], quant)
    return x


def _stacked(block) -> bool:
    return block["mix_norm"]["w"].ndim == 2


@partial(jax.jit, static_argnames=("arch_items", "eps", "quant"))
def _forward(params, tokens, *, arch_items, eps, quant):
    arch = dict(arch_items)
    x = params["embed"]["w"].astype(F32)[tokens]
    for seg in params["segments"]:
        if _stacked(seg[0]):

            def body(x, blocks):
                for blk in blocks:
                    x = _layer(blk, x, arch, eps, quant)
                return x, None

            x, _ = jax.lax.scan(body, x, seg)
        else:
            for blk in seg:
                x = _layer(blk, x, arch, eps, quant)
    x = _rms(x, params["final_norm"]["w"], eps)
    if arch["tie_embeddings"]:
        return _mm(x, params["embed"]["w"].T, quant)
    return _dense(params["head"], x, quant)


def forward(params, tokens, arch: dict, eps: float, quant=None) -> jax.Array:
    """Float32 logits ``(B, S, vocab)`` of ``tokens`` ``(B, S)``."""
    keys = ("n_heads", "n_kv_heads", "head_dim", "d_model", "rope_theta", "tie_embeddings")
    items = tuple((k, arch.get(k)) for k in keys)
    return _forward(params, tokens, arch_items=items, eps=float(eps), quant=quant)


@jax.jit
def _gap(served, ref):
    """Per row: the widest gap by which the served top token's reference logit
    lies below the reference's best, and how many positions agree on it."""
    pick = jnp.argmax(served, axis=-1)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, pick[..., None], axis=-1)[..., 0]
    agree = pick == jnp.argmax(ref, axis=-1)
    return jnp.max(best - got), jnp.sum(agree)


def widest_gap(params, tokens, served, arch: dict, eps: float, *, rows: int = 8,
               quant=None) -> tuple[float, float]:
    """The widest logit gap of ``served`` (B, S, V) against the reference over
    ``tokens`` (B, S), computed ``rows`` rows at a time; and the share of
    positions where the served top token is the reference's.

    With ``served=None`` the reference in ``quant`` precision takes the
    program's place (the control).
    """
    B, S = tokens.shape
    worst, agree = 0.0, 0
    for r0 in range(0, B, rows):
        t = tokens[r0:r0 + rows]
        ref = forward(params, t, arch, eps)
        mine = forward(params, t, arch, eps, quant) if served is None else served[r0:r0 + rows]
        g, a = _gap(mine, ref)
        worst, agree = max(worst, float(g)), agree + int(a)
    return worst, agree / (B * S)
