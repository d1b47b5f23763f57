"""Mixture-of-Experts: dropless sort + grouped-matmul local path and an
expert-parallel (EP) shard_map path with capacity-bounded all_to_all.

TPU adaptation notes (DESIGN.md Sec. 3): instead of a CUDA grouped-GEMM port
we sort tokens by expert and run a grouped matmul for the local computation
(`ops.expert_gmm`: the Pallas megablox kernel on a TPU, named
``expert_gmm`` in the device trace; ``jax.lax.ragged_dot`` elsewhere and in
the EP body), and express expert parallelism as an
explicit shard_map: tokens sharded over the EP axes are routed to expert
owners with a single capacity-padded ``all_to_all`` each way — the TPU-native
analogue of the paper-ecosystem's NCCL all-to-all MoE dispatch.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..kernels import ops
from .layers import Params, _dense_init, mlp_forward, mlp_init


@dataclasses.dataclass(frozen=True)
class MoEMeshInfo:
    """How experts and tokens are distributed.

    ``ep_axes`` are the mesh axes the expert dim is sharded over (the
    all_to_all group); ``token_axes`` are the axes tokens are sharded over —
    a superset when data-parallel replicas (e.g. the 'pod' axis) each run
    their own expert-parallel group.
    """

    ep_axes: tuple[str, ...]  # e.g. ('model',) or ('data', 'model')
    ep_size: int
    token_axes: tuple[str, ...] = ()  # defaults to ep_axes
    token_size: int = 0
    mesh: Any = None  # jax Mesh; None => caller is already inside shard_map
    all_axes: tuple[str, ...] = ()  # every mesh axis name (for aux pmean)

    def __post_init__(self):
        if not self.token_axes:
            object.__setattr__(self, "token_axes", self.ep_axes)
            object.__setattr__(self, "token_size", self.ep_size)


# the routed experts' weights: (E_pad, d, f), (E_pad, f, d) and (E_pad, d, f)
STACKED = ("w1", "w2", "w3")


# --------------------------------------------------------------------- init
def moe_init(key, cfg: ArchConfig, dtype, ep: int = 1) -> Params:
    """Expert weights stored stacked: (E_pad, d, f).  E padded to EP multiple."""
    d, f, E = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    E_pad = -(-E // ep) * ep
    ks = jax.random.split(key, 5)
    scale_in = d ** -0.5
    scale_out = f ** -0.5
    p: Params = {
        "router": _dense_init(ks[0], d, E_pad, dtype),
        "w1": (jax.random.normal(ks[1], (E_pad, d, f)) * scale_in).astype(dtype),
        "w3": (jax.random.normal(ks[2], (E_pad, d, f)) * scale_in).astype(dtype),
        "w2": (jax.random.normal(ks[3], (E_pad, f, d)) * scale_out).astype(dtype),
    }
    if cfg.router_score == "sigmoid_noaux":
        # a dict of its own: under "router" `dense` would add it to the logits
        p["score_bias"] = {"b": jnp.zeros((E_pad,), jnp.float32)}
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], d, f * cfg.n_shared_experts, dtype)
    return p


def route(p: Params, cfg: ArchConfig, x: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Router: top-k ids, their gate weights and a load-balance aux loss.

    Scores are the softmax of the f32 logits, or with ``cfg.router_score``
    ``"sigmoid_noaux"`` a sigmoid per expert, whose top-k are chosen by score
    plus the per-expert correction bias while the gates are the chosen scores
    alone.  The gates are divided by their sum, then multiplied by
    ``cfg.routed_scale``.

    x: (N, d) -> ids (N, k) int32, gates (N, k) f32, aux scalar.
    """
    E = cfg.n_experts
    logits = jnp.dot(x, p["router"]["w"].astype(x.dtype), preferred_element_type=jnp.float32)
    logits = logits[..., :E]  # drop padding experts
    if cfg.router_score == "sigmoid_noaux":
        scores = jax.nn.sigmoid(logits)
        probs = scores / scores.sum(-1, keepdims=True)
        _, ids = jax.lax.top_k(scores + p["score_bias"]["b"][:E], cfg.top_k)
    else:
        scores = probs = jax.nn.softmax(logits, axis=-1)
        _, ids = jax.lax.top_k(scores, cfg.top_k)
    gates = jnp.take_along_axis(scores, ids, axis=-1)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9) * cfg.routed_scale
    # load-balance loss: E * sum_e (fraction routed to e) * (mean prob of e)
    onehot = jax.nn.one_hot(ids, E, dtype=jnp.float32).sum(1)  # (N, E)
    frac = onehot.mean(0) / cfg.top_k
    aux = E * jnp.sum(frac * probs.mean(0))
    return ids, gates, aux


# ------------------------------------------------------------- local (dropless)
def expert_ffn_local(
    p: Params, cfg: ArchConfig, x: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Dropless MoE on one device: sort by expert, grouped matmul, unsort.

    The experts' weights are this layer's, or with ``p["layer"]`` the stack
    of every layer's, of which this layer is entry ``p["layer"]``.

    x: (N, d) -> (N, d), aux loss, expert ids (N, k).
    """
    N = x.shape[0]
    k = cfg.top_k
    E_pad = p["w1"].shape[-3]
    layer = p.get("layer")
    with jax.named_scope("router"):
        ids, gates, aux = route(p, cfg, x)
    with jax.named_scope("experts"):
        flat_ids = ids.reshape(-1)  # (N*k,)
        order = jnp.argsort(flat_ids)
        xs = x[order // k]  # (N*k, d) sorted by expert
        group_sizes = jnp.bincount(flat_ids, length=E_pad)
        h1 = ops.expert_gmm(xs, p["w1"], group_sizes, layer=layer)
        h3 = ops.expert_gmm(xs, p["w3"], group_sizes, layer=layer)
        act = jax.nn.silu(h1) if cfg.act == "silu" else jax.nn.gelu(h1)
        ys = ops.expert_gmm(act * h3, p["w2"], group_sizes, layer=layer)
        with jax.named_scope("combine"):
            # un-sort: (token, choice) slot i sits at row inv[i] of the sorted rows.
            # Gathered in (choice, token) order and summed one choice at a time in
            # f32, the un-sort, gate weighting and sum compile to one pass over the
            # gathered rows, with no f32 or k-padded (N, k, d) copy of them.
            inv = jnp.zeros_like(order).at[order].set(jnp.arange(order.size, dtype=order.dtype))
            yk = ys[inv.reshape(N, k).T]  # (k, N, d)
            y = yk[0].astype(jnp.float32) * gates[:, 0, None]
            for j in range(1, k):
                y = y + yk[j].astype(jnp.float32) * gates[:, j, None]
            out = y.astype(x.dtype)
    return out, aux, ids


# --------------------------------------------------------------- EP shard_map
def expert_ffn_ep(
    p: Params,
    cfg: ArchConfig,
    x: jax.Array,
    mesh_info: MoEMeshInfo,
) -> tuple[jax.Array, jax.Array]:
    """Per-device body (already inside shard_map): route local tokens to the
    expert owners over the flattened EP axes via capacity-padded all_to_all.

    x: (N_loc, d) local tokens.  Expert weights arrive sharded: (E_loc, d, f).
    Returns (N_loc, d), the aux loss and the tokens' global expert ids.
    """
    ep = mesh_info.ep_size
    axes = mesh_info.ep_axes
    N, d = x.shape
    k = cfg.top_k
    E_loc = p["w1"].shape[0]  # local experts per device
    cap = max(1, int(-(-N * k // ep) * cfg.moe_capacity_factor))

    ids, gates, aux = route(p, cfg, x)  # ids are GLOBAL expert ids
    flat_ids = ids.reshape(-1)
    flat_gates = gates.reshape(-1)
    dest = flat_ids // E_loc  # owner device along EP
    order = jnp.argsort(dest)
    # slot within the destination bucket
    sorted_dest = dest[order]
    pos_in_bucket = jnp.arange(N * k) - jnp.searchsorted(
        sorted_dest, sorted_dest, side="left"
    )
    keep = pos_in_bucket < cap  # capacity drop
    # dropped entries go to a trash slot (ep*cap) that is sliced away
    slot = jnp.where(keep, sorted_dest * cap + pos_in_bucket, ep * cap)

    send_x = jnp.zeros((ep * cap + 1, d), x.dtype)
    send_eid = jnp.full((ep * cap + 1,), -1, jnp.int32)  # local expert id at dest
    send_src = jnp.full((ep * cap + 1,), -1, jnp.int32)  # flat (token*k) slot for return
    tok = order // k
    send_x = send_x.at[slot].set(x[tok])
    send_eid = send_eid.at[slot].set((flat_ids[order] % E_loc).astype(jnp.int32))
    send_src = send_src.at[slot].set(order.astype(jnp.int32))
    send_x, send_eid, send_src = send_x[:-1], send_eid[:-1], send_src[:-1]

    a2a = lambda t: jax.lax.all_to_all(
        t.reshape(ep, cap, *t.shape[1:]), axes, split_axis=0, concat_axis=0, tiled=False
    ).reshape(ep * cap, *t.shape[1:])
    recv_x = a2a(send_x)
    recv_eid = a2a(send_eid)

    # local grouped FFN over received tokens (invalid rows go to a trash group)
    eid = jnp.where(recv_eid < 0, E_loc, recv_eid)
    lorder = jnp.argsort(eid)
    xs = recv_x[lorder]
    group_sizes = jnp.bincount(eid, length=E_loc + 1)[:E_loc]
    # rows beyond sum(group_sizes) fall out of every group -> ragged_dot zeros
    h1 = jax.lax.ragged_dot(xs, p["w1"].astype(x.dtype), group_sizes)
    h3 = jax.lax.ragged_dot(xs, p["w3"].astype(x.dtype), group_sizes)
    act = jax.nn.silu(h1) if cfg.act == "silu" else jax.nn.gelu(h1)
    ys = jax.lax.ragged_dot(act * h3, p["w2"].astype(x.dtype), group_sizes)
    y = jnp.zeros_like(recv_x).at[lorder].set(ys)

    back = a2a(y)  # back to the source device, same slot order as send_x
    out = jnp.zeros((N, d), x.dtype)
    valid = send_src >= 0
    contrib = back * jnp.where(valid, flat_gates[send_src], 0.0)[:, None].astype(x.dtype)
    out = out.at[jnp.where(valid, send_src // k, 0)].add(
        jnp.where(valid[:, None], contrib, 0.0)
    )
    # aux loss averaged over the whole mesh (fully replicated output)
    aux = jax.lax.pmean(aux, mesh_info.all_axes or axes)  # fully replicated
    return out, aux, ids


def moe_forward(
    p: Params,
    cfg: ArchConfig,
    x: jax.Array,  # (B, S, d)
    *,
    mesh_info: MoEMeshInfo | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """-> (B, S, d), the aux loss, and the routes: expert ids (B*S, top_k)."""
    B, S, d = x.shape
    flat = x.reshape(B * S, d)
    if mesh_info is None:
        y, aux, ids = expert_ffn_local(p, cfg, flat)
    elif mesh_info.mesh is None:
        y, aux, ids = expert_ffn_ep(p, cfg, flat, mesh_info)
    else:
        y, aux, ids = _moe_shard_mapped(p, cfg, flat, mesh_info)
    if "shared" in p:
        with jax.named_scope("shared_experts"):
            y = y + mlp_forward(p["shared"], flat, cfg.act)
    return y.reshape(B, S, d), aux, ids


def _moe_shard_mapped(
    p: Params, cfg: ArchConfig, flat: jax.Array, info: MoEMeshInfo
) -> tuple[jax.Array, jax.Array]:
    """Wrap the EP body in shard_map over the full mesh.

    Tokens are sharded over the flattened EP axes; expert weights over their
    expert dim; the router is replicated.  Token counts that do not divide
    the EP degree (e.g. single-token decode) are zero-padded.
    """
    from jax.sharding import PartitionSpec as P

    N, d = flat.shape
    pad = (-N) % info.token_size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad, d), flat.dtype)], 0)
    ep_t = info.ep_axes if len(info.ep_axes) > 1 else info.ep_axes[0]
    tok_t = info.token_axes if len(info.token_axes) > 1 else info.token_axes[0]
    p_ep = {k: p[k] for k in ("router", "w1", "w2", "w3", "score_bias") if k in p}
    in_specs = (
        {
            "router": P(None, None),
            "w1": P(ep_t, None, None),
            "w2": P(ep_t, None, None),
            "w3": P(ep_t, None, None),
            **({"score_bias": P(None)} if "score_bias" in p else {}),
        },
        P(tok_t, None),
    )
    body = lambda pp, xx: expert_ffn_ep(pp, cfg, xx, info)
    fn = jax.shard_map(
        body,
        mesh=info.mesh,
        in_specs=in_specs,
        out_specs=(P(tok_t, None), P(), P(tok_t, None)),
        check_vma=False,
    )
    y, aux, ids = fn(p_ep, flat)
    if pad:
        y, ids = y[:N], ids[:N]
    return y, aux, ids
