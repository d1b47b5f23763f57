"""Sharding rules + an 8-device end-to-end sharded train/decode (subprocess)."""
import os
import subprocess
import sys
import textwrap

import jax
import pytest

# JAX-compile-heavy (jits real kernels/models); deselect with -m "not slow"
pytestmark = pytest.mark.slow

from repro.configs import ARCHS, SHAPES
from repro.launch.shardings import divisibility_fix, param_spec
from repro.models import Model
from jax.sharding import PartitionSpec as P


def test_param_spec_rules():
    cfg = ARCHS["deepseek-v3-671b"]

    class L:  # fake leaf
        def __init__(self, shape):
            self.shape = shape
            self.ndim = len(shape)

    # stacked expert weights: EP on the expert dim (-3), not the repeats dim
    spec = param_spec(
        "segments/1/0/ffn/w1", L((58, 256, 7168, 2048)), cfg,
        ep_axes=("data", "model"), fsdp=False, ep=256,
    )
    assert spec == P(None, ("data", "model"), None, None)
    # attention projections: column-parallel
    spec = param_spec("segments/0/0/mix/q_b/w", L((58, 1536, 24576)), cfg,
                      ep_axes=(), fsdp=False)
    assert spec[-1] == "model"
    # norms replicated
    spec = param_spec("segments/0/0/mix_norm/w", L((58, 7168)), cfg,
                      ep_axes=(), fsdp=False)
    assert all(e is None for e in spec)


def test_divisibility_fix():
    class L:
        shape = (2, 8)
        ndim = 2

    fixed = divisibility_fix(P(None, "model"), L(), {"model": 16})
    assert fixed == P(None, None)
    fixed = divisibility_fix(P(None, "model"), L(), {"model": 8})
    assert fixed == P(None, "model")


_SHARDED_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.configs import SMOKE_ARCHS
    from repro.models import Model
    from repro.launch.shardings import param_specs, to_shardings
    from repro.training import OptConfig, adamw_init, make_train_step
    from repro.data import lm_batches

    cfg = SMOKE_ARCHS["smollm-360m"]
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    specs = param_specs(jax.eval_shape(lambda: params), cfg, mesh=mesh)
    shardings = to_shardings(specs, mesh)
    params = jax.device_put(params, shardings)
    opt = adamw_init(params)
    step = jax.jit(make_train_step(model, OptConfig(lr=1e-3, total_steps=4)))
    batch = next(lm_batches(cfg.vocab_size, 4, 16))
    with mesh:
        batch = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
        l0 = None
        for i in range(4):
            params, opt, metrics = step(params, opt, batch)
            if l0 is None:
                l0 = float(metrics["loss"])
        l1 = float(metrics["loss"])
    assert l1 < l0, (l0, l1)
    print("SHARDED-TRAIN-OK", l0, "->", l1)

    # sharded decode consistency vs single-device forward
    toks = jax.random.randint(jax.random.key(1), (4, 10), 0, cfg.vocab_size)
    full = model.forward(params, toks)
    cache = model.init_cache(4, 16)
    pre = model.forward(params, toks[:, :9], cache=cache, idx=0)
    dec = model.forward(params, toks[:, 9:], cache=pre.cache, idx=9)
    err = float(jnp.max(jnp.abs(full.logits[:, -1] - dec.logits[:, 0])))
    rel = err / (float(jnp.max(jnp.abs(full.logits[:, -1]))) + 1e-9)
    assert rel < 2e-3, rel
    print("SHARDED-DECODE-OK", rel)
    """
)


def test_sharded_train_and_decode_8_devices():
    r = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT],
        capture_output=True,
        text=True,
        timeout=560,
        cwd=".",
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SHARDED-TRAIN-OK" in r.stdout
    assert "SHARDED-DECODE-OK" in r.stdout
