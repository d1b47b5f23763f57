"""Plain float32 reference of a DeepSeek-V3-block LM (Moonlight-16B-A3B).

It follows the published description (DeepSeek-V3 technical report,
arXiv:2412.19437, section 2.1, and the model's ``config.json``) and imports
nothing of the program: token embedding; per layer, RMSNorm and multi-head
latent attention without a query LoRA (queries ``h W_q`` split per head into a
position-free part and a rotary part; a latent ``c = RMSNorm(h W_a[:, :r])``
and one rotary key ``h W_a[:, r:]`` shared by the heads; per-head keys
``c W_uk`` and values ``c W_uv``; causal softmax attention over
``[q_nope ; q_rope] . [k_nope ; k_rope] / sqrt(d_nope + d_rope)``), output
projection and residual; RMSNorm, then the first layer's gated SiLU MLP, or
the mixture of experts: scores ``s = sigmoid(h W_r)`` per expert, the top-k
of ``s + bias`` chosen (the bias selects and weighs nothing), gate weights
the chosen ``s`` over their sum times ``routed_scale``, each routed expert a
gated SiLU MLP, plus the shared experts unweighted; residual; a final
RMSNorm and the untied unembedding.  Every matrix multiplication runs at
``Precision.HIGHEST``, each layer's weights are made float32 one layer (and
one expert) at a time, and rows are computed a block at a time, so that it
fits on the chip beside the program.

Departures from the published model, none of which changes the function
computed for the benchmark's weights:

* RoPE turns the rotary dimensions as they are stored, in the rotate-half
  layout; the published code first de-interleaves them, which is the same map
  after a fixed permutation of the rotary columns of ``W_q`` and ``W_a``;
* every RMSNorm takes the configuration's ``eps`` (the file's
  ``rms_norm_eps``, as run);
* the gate weights are divided by their plain sum (published: sum + 1e-20);
* each routed expert runs on every token, with a gate weight of zero where
  the token did not choose it: the same sum, in a plain form;
* the routes are given: ``widest_gap`` runs the forward on the program's
  routes (see there), and the control on the reference's own.

``quant`` replaces each weight matmul's two operands by their values in a
lower precision: the control of the comparison (``fp8``).
"""
from __future__ import annotations

import sys
from functools import partial

import jax
import jax.numpy as jnp

from bench.references.dense_lm import F32, HI, _gap, _rms, fp8  # noqa: F401  (fp8: the control)

# How far below the reference's k-th largest biased score (score + bias) a
# routed expert's biased score may lie.  The program computes the router's
# input in bfloat16, so its scores differ from these by up to some e, and an
# expert it picks then lies at most 2e below the reference's k-th.  Measured
# on a TPU v5 lite at Moonlight-16B-A3B's widths (12 seeds x b1-b32 and 6
# more runs): the served program's widest shortfall 0.0184, so 2e reaches
# about 0.02; a router that leaves the bias out of the selection reads
# 0.357-0.403, one with softmax scores 0.686-0.745.  0.08 lies between, with
# room of about 4x on both sides.
ROUTE_MARGIN = 0.08


def _mm(x, w, quant):
    w = w.astype(F32)
    if quant is not None:
        x, w = quant(x), quant(w)
    return jnp.matmul(x, w, precision=HI)


def _rope(x, theta):
    """Rotate-half rotary embedding of ``x`` (B, S, H, D) at positions 0..S-1."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv  # (S, D/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _mla(a, h, arch, eps, quant):
    B, S, _ = h.shape
    H, dn, dv = arch["n_heads"], arch["head_dim"], arch["v_head_dim"]
    dc, dr = arch["kv_lora_rank"], arch["rope_head_dim"]
    q = _mm(h, a["q_b"]["w"], quant).reshape(B, S, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], arch["rope_theta"])], -1)
    kv = _mm(h, a["kv_a"]["w"], quant)
    c = _rms(kv[..., :dc], a["kv_norm"]["w"], eps)
    k_rope = _rope(kv[..., dc:][:, :, None], arch["rope_theta"])  # (B, S, 1, dr)
    k_nope = _mm(c, a["k_b"]["w"], quant).reshape(B, S, H, dn)
    v = _mm(c, a["v_b"]["w"], quant).reshape(B, S, H, dv)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, dr))], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * (dn + dr) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, precision=HI)
    return _mm(o.reshape(B, S, H * dv), a["o"]["w"], quant)


def _mlp(f, x, quant):
    gate = jax.nn.silu(_mm(x, f["w1"]["w"], quant))
    return _mm(gate * _mm(x, f["w3"]["w"], quant), f["w2"]["w"], quant)


def _moe(f, x, routes, arch, quant):
    """The expert layer on tokens ``x`` (N, d).  ``routes`` (N, k) are the
    experts to use, or None for the reference's own choice.  Returns the
    output, the routes used, the reference's own top-k, and the shortfall:
    the widest gap by which a used expert's biased score lies below the
    reference's k-th largest (inf for an id out of range or chosen twice)."""
    E, k = arch["n_experts"], arch["top_k"]
    s = jax.nn.sigmoid(_mm(x, f["router"]["w"], quant))[:, :E]
    biased = s + f["score_bias"]["b"][:E].astype(F32)
    top, own = jax.lax.top_k(biased, k)
    if routes is None:
        routes = own
    ids = jnp.sort(routes, axis=-1)
    bad = (ids[:, 0] < 0) | (ids[:, -1] >= E) | jnp.any(ids[:, 1:] == ids[:, :-1], axis=-1)
    short = top[:, -1] - jnp.take_along_axis(biased, jnp.clip(routes, 0, E - 1), -1).min(-1)
    shortfall = jnp.max(jnp.where(bad, jnp.inf, short))
    g = jnp.take_along_axis(s, jnp.clip(routes, 0, E - 1), -1)
    g = g / g.sum(-1, keepdims=True) * arch["routed_scale"]
    gate = jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None], routes].add(g)  # (N, E)

    def expert(acc, e):
        w = {n: {"w": f[n][e]} for n in ("w1", "w2", "w3")}
        return acc + gate[:, e, None] * _mlp(w, x, quant), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(E))
    return y + _mlp(f["shared"], x, quant), routes, own, shortfall


def _layer(p, x, routes, arch, eps, quant):
    B, S, d = x.shape
    x = x + _mla(p["mix"], _rms(x, p["mix_norm"]["w"], eps), arch, eps, quant)
    h = _rms(x, p["ffn_norm"]["w"], eps)
    if "router" not in p["ffn"]:
        return x + _mlp(p["ffn"], h, quant), None
    y, used, own, shortfall = _moe(p["ffn"], h.reshape(B * S, d), routes, arch, quant)
    differ = jnp.any(jnp.sort(used, -1) != jnp.sort(own, -1), axis=-1).sum()
    return x + y.reshape(B, S, d), (own, shortfall, differ)


def _pattern(blocks, x, routes, arch, eps, quant):
    """The blocks of one pattern in order; ``routes`` (m, N, k) for its m
    expert layers, or None.  Returns x and, stacked over those m layers, the
    reference's own routes, the shortfalls and the counts of ``_layer``."""
    stats = []
    for blk in blocks:
        moe = "router" in blk["ffn"]
        rr = routes[len(stats)] if moe and routes is not None else None
        x, st = _layer(blk, x, rr, arch, eps, quant)
        if moe:
            stats.append(st)
    if not stats:
        n = x.shape[0] * x.shape[1]
        return x, (jnp.zeros((0, n, arch["top_k"]), jnp.int32), jnp.zeros((0,), F32),
                   jnp.zeros((0,), jnp.int32))
    return x, tuple(jnp.stack(v) for v in zip(*stats))


def _stacked(block) -> bool:
    return block["mix_norm"]["w"].ndim == 2


@partial(jax.jit, static_argnames=("arch_items", "eps", "quant"))
def _forward(params, tokens, routes, *, arch_items, eps, quant):
    arch = dict(arch_items)
    x = params["embed"]["w"][tokens].astype(F32)
    own, shortfall, differ, i = [], [], [], 0
    for seg in params["segments"]:
        m = sum("router" in blk["ffn"] for blk in seg)
        reps = seg[0]["mix_norm"]["w"].shape[0] if _stacked(seg[0]) else 1
        rr = None if routes is None else routes[i:i + reps * m]
        if _stacked(seg[0]):
            rr = None if rr is None else rr.reshape(reps, m, *rr.shape[1:])
            x, (o, d, c) = jax.lax.scan(
                lambda x, xs: _pattern(xs[0], x, xs[1], arch, eps, quant), x, (seg, rr))
            o = o.reshape(reps * m, *o.shape[2:])  # repeat-major: layer order
        else:
            x, (o, d, c) = _pattern(seg, x, rr, arch, eps, quant)
        own.append(o)
        shortfall.append(d.reshape(-1))
        differ.append(c.reshape(-1))
        i += reps * m
    x = _rms(x, params["final_norm"]["w"], eps)
    return (_mm(x, params["head"]["w"], quant), jnp.concatenate(own),
            jnp.max(jnp.concatenate(shortfall), initial=0.0), jnp.concatenate(differ).sum())


def forward(params, tokens, arch: dict, eps: float, routes=None, quant=None):
    """Float32 logits ``(B, S, vocab)`` of ``tokens`` ``(B, S)``, with the
    experts of ``routes`` (``(n_moe_layers, B*S, top_k)``, the program's) or,
    without them, the reference's own choice.  Also returns the reference's
    own routes, the widest gap by which a used expert's biased score lies
    below the reference's k-th largest, and the number of (layer, token)
    whose used experts are not the reference's own top-k."""
    keys = ("n_heads", "head_dim", "v_head_dim", "kv_lora_rank", "rope_head_dim",
            "rope_theta", "n_experts", "top_k", "routed_scale")
    items = tuple((k, arch[k]) for k in keys)
    return _forward(params, tokens, routes, arch_items=items, eps=float(eps), quant=quant)


def widest_gap(params, tokens, served, arch: dict, eps: float, *, rows: int = 4,
               quant=None) -> tuple[float, float]:
    """The widest logit gap of ``served = (logits, routes)`` against the
    reference over ``tokens`` (B, S), computed ``rows`` rows at a time, and the
    share of positions where the served top token is the reference's.

    The routes decide nothing by themselves: random weights leave the k-th
    and (k+1)-th biased scores of many tokens closer than bfloat16 rounding of
    the router's input, so the reference runs on the program's routes, and
    they are checked first: every expert the program chose for a token in a
    layer must lie within ``ROUTE_MARGIN`` of the reference's k-th largest
    biased score there (one that does not, or routes of the wrong shape, read
    as a gap of 1e30).  The gate weights are the reference's own.

    With ``served=None`` the reference in ``quant`` precision, on the float32
    reference's own routes, takes the program's place (the control).
    """
    B, S = tokens.shape
    worst, agree, shortfall, differ = 0.0, 0, 0.0, 0
    if served is not None:
        logits, routes = served
        n_moe = arch["n_layers"] - arch["n_dense_layers"]
        if routes.shape != (n_moe, B * S, arch["top_k"]):
            print(f"route check: routes of shape {routes.shape}", file=sys.stderr)
            return 1e30, 0.0
    for r0 in range(0, B, rows):
        t = tokens[r0:r0 + rows]
        if served is None:
            ref, own, _, _ = forward(params, t, arch, eps)
            mine = forward(params, t, arch, eps, routes=own, quant=quant)[0]
        else:
            blk = routes[:, r0 * S:(r0 + rows) * S]
            ref, _, d, c = forward(params, t, arch, eps, routes=blk)
            mine = logits[r0:r0 + rows]
            shortfall, differ = max(shortfall, float(d)), differ + int(c)
        g, a = _gap(mine, ref)
        worst, agree = max(worst, float(g)), agree + int(a)
    if served is not None:
        share = differ / routes.shape[0] / (B * S)
        print(f"route check: widest shortfall {shortfall!r} (margin {ROUTE_MARGIN!r}); "
              f"{share!r} of (layer, token) off the reference's own top-{arch['top_k']}",
              file=sys.stderr, flush=True)
        if not shortfall <= ROUTE_MARGIN:
            worst = 1e30
    return worst, agree / (B * S)
