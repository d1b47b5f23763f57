"""Composable decoder model: segments of repeated layer blocks under lax.scan.

The layer-spec sequence of an architecture (configs.base.ArchConfig) is
compressed into *segments* — (pattern, repeats) with a small repeating
pattern — so heterogeneous stacks (Jamba's 1:7 attn:mamba macro-block,
gemma3's 5:1 local:global, deepseek's 3 dense + 58 MoE) all scan over
stacked parameters with a compact HLO, which keeps 512-device SPMD compiles
tractable and enables per-macro-block remat.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig, LayerSpec
from . import layers as Lyr
from . import moe as Moe
from . import ssm as Ssm
from .moe import MoEMeshInfo

Params = dict[str, Any]


# ----------------------------------------------------------------- segments
def segmentize(specs: tuple[LayerSpec, ...]) -> list[tuple[tuple[LayerSpec, ...], int]]:
    """Compress a layer-spec list into (pattern, repeats) segments."""
    out: list[tuple[tuple[LayerSpec, ...], int]] = []
    i, n = 0, len(specs)
    while i < n:
        best_p, best_r = 1, 1
        for p in range(1, min(8, n - i) + 1):
            pat = specs[i : i + p]
            r = 1
            while specs[i + r * p : i + (r + 1) * p] == pat:
                r += 1
            if r > 1 and p * r > best_p * best_r:
                best_p, best_r = p, r
        if best_r == 1:
            # literal run: absorb consecutive non-repeating layers
            j = i + 1
            out.append((specs[i:j], 1))
            i = j
        else:
            out.append((specs[i : i + best_p], best_r))
            i += best_p * best_r
    # merge adjacent literal singletons into one unrolled pattern
    merged: list[tuple[tuple[LayerSpec, ...], int]] = []
    for pat, r in out:
        if r == 1 and merged and merged[-1][1] == 1:
            merged[-1] = (merged[-1][0] + pat, 1)
        else:
            merged.append((pat, r))
    return merged


# ------------------------------------------------------------------- blocks
def _block_init(key, cfg: ArchConfig, spec: LayerSpec, dtype, ep: int) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Params = {"mix_norm": Lyr.norm_init(cfg, cfg.d_model, dtype)}
    if spec.mixer == "attn":
        p["mix"] = Lyr.attn_init(k1, cfg, dtype)
    elif spec.mixer == "mla":
        p["mix"] = Lyr.mla_init(k1, cfg, dtype)
    elif spec.mixer == "mamba":
        p["mix"] = Ssm.mamba_init(k1, cfg, dtype)
    elif spec.mixer == "mlstm":
        p["mix"] = Ssm.mlstm_init(k1, cfg, dtype)
    elif spec.mixer == "slstm":
        p["mix"] = Ssm.slstm_init(k1, cfg, dtype)
    else:
        raise ValueError(spec.mixer)
    if spec.ffn != "none":
        p["ffn_norm"] = Lyr.norm_init(cfg, cfg.d_model, dtype)
        if spec.ffn == "moe":
            p["ffn"] = Moe.moe_init(k2, cfg, dtype, ep)
        else:
            p["ffn"] = Lyr.mlp_init(k2, cfg.d_model, cfg.d_ff, dtype)
    return p


def _block_cache_init(cfg: ArchConfig, spec: LayerSpec, batch: int, max_seq: int, dtype):
    if spec.mixer == "attn":
        return Lyr.attn_cache_init(cfg, spec, batch, max_seq, dtype)
    if spec.mixer == "mla":
        return Lyr.mla_cache_init(cfg, batch, max_seq, dtype)
    if spec.mixer == "mamba":
        return Ssm.mamba_cache_init(cfg, batch, dtype)
    if spec.mixer == "mlstm":
        return Ssm.mlstm_cache_init(cfg, batch, dtype)
    if spec.mixer == "slstm":
        return Ssm.slstm_cache_init(cfg, batch, dtype)
    raise ValueError(spec.mixer)


def _block_apply(
    p: Params,
    cfg: ArchConfig,
    spec: LayerSpec,
    x: jax.Array,
    positions: jax.Array,
    cache,
    idx,
    mesh_info: MoEMeshInfo | None,
):
    h = Lyr.apply_norm(cfg, p["mix_norm"], x)
    if spec.mixer == "attn":
        y, new_cache = Lyr.attn_forward(p["mix"], cfg, spec, h, positions, cache=cache, idx=idx)
    elif spec.mixer == "mla":
        y, new_cache = Lyr.mla_forward(p["mix"], cfg, h, positions, cache=cache, idx=idx)
    elif spec.mixer == "mamba":
        y, new_cache = Ssm.mamba_forward(p["mix"], cfg, h, cache=cache)
    elif spec.mixer == "mlstm":
        y, new_cache = Ssm.mlstm_forward(p["mix"], cfg, h, cache=cache)
    else:
        y, new_cache = Ssm.slstm_forward(p["mix"], cfg, h, cache=cache)
    from ..kernels.ops import constrain_activations

    x = constrain_activations(x + y)
    aux = jnp.zeros((), jnp.float32)
    routes = None
    if spec.ffn != "none":
        h = Lyr.apply_norm(cfg, p["ffn_norm"], x)
        if spec.ffn == "moe":
            y, aux, routes = Moe.moe_forward(p["ffn"], cfg, h, mesh_info=mesh_info)
        else:
            y = Lyr.mlp_forward(p["ffn"], h, cfg.act)
        x = constrain_activations(x + y)
    return x, new_cache, aux, routes


@dataclass
class ModelOutput:
    logits: jax.Array | None
    cache: Any
    aux_loss: jax.Array
    hidden: jax.Array | None = None
    # expert ids (n_moe_layers, B*S, top_k) int32, in layer order; None
    # for an arch without MoE layers
    routes: jax.Array | None = None


class Model:
    """Pure-function model; parameters are plain dict pytrees."""

    def __init__(self, cfg: ArchConfig, mesh_info: MoEMeshInfo | None = None):
        self.cfg = cfg
        self.mesh_info = mesh_info
        self.specs = cfg.layer_specs()
        self.segments = segmentize(self.specs)
        self.pdtype = jnp.dtype(cfg.param_dtype)
        self.cdtype = jnp.dtype(cfg.compute_dtype)

    # ------------------------------------------------------------------ init
    def init(self, key: jax.Array) -> Params:
        cfg = self.cfg
        ep = self.mesh_info.ep_size if self.mesh_info else 1
        keys = jax.random.split(key, len(self.segments) + 3)
        params: Params = {"embed": Lyr.embed_init(keys[0], cfg, self.pdtype)}
        segs = []
        for si, (pattern, repeats) in enumerate(self.segments):
            kseg = keys[si + 1]

            def init_one(k):
                ks = jax.random.split(k, len(pattern))
                return tuple(
                    _block_init(ks[j], cfg, spec, self.pdtype, ep)
                    for j, spec in enumerate(pattern)
                )

            if repeats == 1:
                segs.append(init_one(kseg))
            else:
                segs.append(jax.vmap(init_one)(jax.random.split(kseg, repeats)))
        params["segments"] = segs
        params["final_norm"] = Lyr.norm_init(cfg, cfg.d_model, self.pdtype)
        if not cfg.tie_embeddings:
            params["head"] = Lyr._dense_init(keys[-1], cfg.d_model, cfg.vocab_size, self.pdtype)
        if cfg.mtp_depth:
            params["mtp"] = {
                "proj": Lyr._dense_init(keys[-2], 2 * cfg.d_model, cfg.d_model, self.pdtype),
                "block": _block_init(
                    keys[-2], cfg, LayerSpec("attn" if cfg.attn_kind != "mla" else "mla", "dense"), self.pdtype, ep
                ),
                "norm": Lyr.norm_init(cfg, cfg.d_model, self.pdtype),
            }
        return params

    # ----------------------------------------------------------------- cache
    def init_cache(self, batch: int, max_seq: int, dtype=None):
        dtype = dtype or self.cdtype
        caches = []
        for pattern, repeats in self.segments:
            one = tuple(
                _block_cache_init(self.cfg, spec, batch, max_seq, dtype)
                for spec in pattern
            )
            if repeats == 1:
                caches.append(one)
            else:
                caches.append(
                    jax.tree.map(
                        lambda x: jnp.broadcast_to(x, (repeats, *x.shape)), one
                    )
                )
        return caches

    # --------------------------------------------------------------- forward
    def forward(
        self,
        params: Params,
        tokens: jax.Array | None = None,
        *,
        embeds: jax.Array | None = None,
        positions: jax.Array | None = None,
        cache=None,
        idx=None,
        return_hidden: bool = False,
        compute_logits: bool = True,
        hold_experts: bool = False,
    ) -> ModelOutput:
        """``hold_experts`` reads each scanned expert layer's weights in place
        (`_hold_experts`); for a forward that no gradient is taken through,
        since each layer's gradient would then be the whole stack's shape."""
        cfg = self.cfg
        if embeds is None:
            x = Lyr.embed(params["embed"], cfg, tokens, self.cdtype)
        else:
            x = embeds.astype(self.cdtype)
        B, S, _ = x.shape
        if positions is None:
            base = jnp.arange(S)[None, :] + (idx if idx is not None else 0)
            positions = jnp.broadcast_to(base, (B, S))
            if cfg.mrope_sections is not None:
                positions = jnp.broadcast_to(positions, (3, B, S))

        aux_total = jnp.zeros((), jnp.float32)
        new_caches = [] if cache is not None else None
        routes = []  # (layers, B*S, top_k) blocks of the MoE layers, in order
        for si, (pattern, repeats) in enumerate(self.segments):
            seg_params = params["segments"][si]
            seg_cache = cache[si] if cache is not None else None

            def apply_pattern(x, blk_params, blk_cache):
                new_bc, rs = [], []
                aux = jnp.zeros((), jnp.float32)
                for j, spec in enumerate(pattern):
                    c_j = blk_cache[j] if blk_cache is not None else None
                    x, nc, a, r = _block_apply(
                        blk_params[j], cfg, spec, x, positions, c_j, idx, self.mesh_info
                    )
                    new_bc.append(nc)
                    aux = aux + a
                    if r is not None:
                        rs.append(r)
                return x, tuple(new_bc), aux, tuple(rs)

            if cfg.remat:
                apply_pattern = jax.checkpoint(apply_pattern)

            if repeats == 1:
                x, nc, aux, rs = apply_pattern(x, seg_params, seg_cache)
                aux_total = aux_total + aux
                routes += [r[None] for r in rs]
                if new_caches is not None:
                    new_caches.append(nc)
            else:
                seg_params, held = (
                    self._hold_experts(pattern, seg_params) if hold_experts else (seg_params, {})
                )

                def scan_body(carry, xs):
                    x, aux_acc = carry
                    i, blk_params, blk_cache = xs
                    blk_params = tuple(
                        {**b, "ffn": {**b["ffn"], **held[j], "layer": i}} if j in held else b
                        for j, b in enumerate(blk_params)
                    )
                    x, nc, aux, rs = apply_pattern(x, blk_params, blk_cache)
                    return (x, aux_acc + aux), (nc, rs)

                (x, aux_total), (nc, rs) = jax.lax.scan(
                    scan_body, (x, aux_total), (jnp.arange(repeats), seg_params, seg_cache)
                )
                if rs:  # (repeats, B*S, k) per MoE layer of the pattern
                    r = jnp.stack(rs, axis=1)
                    routes.append(r.reshape(-1, *r.shape[2:]))
                if new_caches is not None:
                    new_caches.append(nc)

        x = Lyr.apply_norm(cfg, params["final_norm"], x)
        hidden = x if return_hidden else None
        logits = None
        if compute_logits:
            logits = self.unembed(params, x)
        routes = jnp.concatenate(routes) if routes else None
        return ModelOutput(logits, new_caches, aux_total, hidden, routes)

    def _hold_experts(self, pattern, seg_params):
        """Take the routed experts' stacked weights out of a scanned segment.

        The scan would slice one layer's experts out of the stack each step,
        and XLA copies such a slice whole before a Pallas call can read it
        (3 x 369 MB a Moonlight layer, a quarter of a b32 forward on a v5e).
        Held out, the expert layer gets the whole stack and its own index
        (``ffn["layer"]``) and reads its experts in place
        (`ops.expert_gmm`).  The EP path takes its shards as they come."""
        if self.mesh_info is not None:
            return seg_params, {}
        held, rest = {}, []
        for j, (spec, blk) in enumerate(zip(pattern, seg_params)):
            if spec.ffn == "moe":
                held[j] = {n: blk["ffn"][n] for n in Moe.STACKED}
                blk = {**blk, "ffn": {k: v for k, v in blk["ffn"].items() if k not in held[j]}}
            rest.append(blk)
        return tuple(rest), held

    def unembed(self, params: Params, x: jax.Array) -> jax.Array:
        if self.cfg.tie_embeddings:
            return x @ params["embed"]["w"].astype(x.dtype).T
        return Lyr.dense(params["head"], x)
