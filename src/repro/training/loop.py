"""Training loop: causal-LM loss (+ MoE load-balance aux, + deepseek MTP)."""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp

from ..models import Model
from ..models import layers as Lyr
from ..models.model import _block_apply
from ..configs.base import LayerSpec
from .optimizer import OptConfig, adamw_init, adamw_update


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token CE; labels < 0 are masked out."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1
    )[..., 0]
    nll = logz - gold
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _mtp_loss(model: Model, params, hidden, tokens, labels) -> jax.Array:
    """DeepSeek multi-token prediction: predict t+2 from h_t and emb(t+1)."""
    cfg = model.cfg
    emb_next = Lyr.embed(params["embed"], cfg, tokens[:, 1:], hidden.dtype)
    h = jnp.concatenate([hidden[:, :-1], emb_next], axis=-1)
    h = Lyr.dense(params["mtp"]["proj"], h)
    B, S1, _ = h.shape
    pos = jnp.broadcast_to(jnp.arange(S1)[None], (B, S1))
    if cfg.mrope_sections is not None:
        pos = jnp.broadcast_to(pos, (3, B, S1))
    spec = LayerSpec("attn" if cfg.attn_kind != "mla" else "mla", "dense")
    h, *_ = _block_apply(params["mtp"]["block"], cfg, spec, h, pos, None, None, model.mesh_info)
    h = Lyr.apply_norm(cfg, params["mtp"]["norm"], h)
    if cfg.tie_embeddings:
        logits = h @ params["embed"]["w"].astype(h.dtype).T
    else:
        logits = Lyr.dense(params["head"], h)
    return cross_entropy(logits[:, :-1], labels[:, 2:])


def make_loss_fn(model: Model, *, aux_coef: float | None = None, mtp_coef: float = 0.3):
    cfg = model.cfg
    aux_coef = cfg.router_aux_coef if aux_coef is None else aux_coef

    def loss_fn(params, batch):
        tokens = batch.get("tokens")
        embeds = batch.get("embeds")
        labels = batch["labels"]
        out = model.forward(
            params,
            tokens,
            embeds=embeds,
            return_hidden=cfg.mtp_depth > 0,
        )
        loss = cross_entropy(out.logits, labels)
        metrics = {"ce": loss}
        if cfg.is_moe_arch:
            n_moe = sum(1 for s in cfg.layer_specs() if s.ffn == "moe")
            aux = out.aux_loss / jnp.maximum(n_moe, 1)
            loss = loss + aux_coef * aux
            metrics["aux"] = aux
        if cfg.mtp_depth and tokens is not None:
            mtp = _mtp_loss(model, params, out.hidden, tokens, labels)
            loss = loss + mtp_coef * mtp
            metrics["mtp"] = mtp
        metrics["loss"] = loss
        return loss, metrics

    return loss_fn


def make_train_step(model: Model, opt_cfg: OptConfig, **loss_kw) -> Callable:
    loss_fn = make_loss_fn(model, **loss_kw)

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        params, opt_state, opt_metrics = adamw_update(opt_cfg, params, grads, opt_state)
        metrics.update(opt_metrics)
        return params, opt_state, metrics

    return train_step


@dataclass
class TrainResult:
    params: Any
    opt_state: Any
    history: list[dict[str, float]]


def train(
    model: Model,
    batches: Iterator[dict[str, jax.Array]],
    steps: int,
    opt_cfg: OptConfig | None = None,
    *,
    seed: int = 0,
    log_every: int = 10,
    log: Callable[[str], None] = print,
) -> TrainResult:
    opt_cfg = opt_cfg or OptConfig(total_steps=steps)
    params = model.init(jax.random.key(seed))
    opt_state = adamw_init(params)
    step_fn = jax.jit(make_train_step(model, opt_cfg))
    history = []
    for i in range(steps):
        batch = next(batches)
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if i % log_every == 0 or i == steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            history.append({"step": i, **m})
            log(f"step {i:5d} " + " ".join(f"{k}={v:.4f}" for k, v in m.items()))
    return TrainResult(params, opt_state, history)
