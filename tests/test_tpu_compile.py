"""Main-path Pallas kernels compile for a described TPU v5e at served widths.

Nothing runs: each test lowers and compiles one kernel for one chip of a
``v5e:2x2`` topology description and asserts the compiled program holds the
kernel (``tpu_custom_call``).  This catches block shapes and VMEM use the
chip's compiler refuses, which interpret mode cannot.  The topology is
described only inside the fixture, so collection is identical on every
worker and only the worker that runs this file loads the TPU compiler.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS
from repro.kernels import ops
from repro.kernels.decode_attention import flash_decode
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import fused_rmsnorm
from repro.models.moe import expert_ffn_local


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, one_chip, *shapes):
    args = [
        jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


BF16 = jnp.bfloat16


@pytest.mark.parametrize(
    "B,S,Hq,Hkv,D,window",
    [
        (8, 128, 15, 5, 64, None),  # smollm-360m
        (2, 1024, 4, 1, 256, 512),  # gemma3-1b local layer
        (32, 128, 15, 5, 64, None),  # smollm-360m widths at b32
        (32, 128, 20, 20, 128, None),  # qwen1.5-4b widths at b32
    ],
    ids=["smollm", "gemma3-local", "smollm-b32", "qwen-b32"],
)
def test_flash_attention_compiles(one_chip, B, S, Hq, Hkv, D, window):
    txt = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, window=window),
        one_chip,
        ((B, S, Hq, D), BF16), ((B, S, Hkv, D), BF16), ((B, S, Hkv, D), BF16),
    )
    # one pallas_call per attention call: one flash_attention.N trace event
    assert txt.count("tpu_custom_call") == 1


def test_fused_rmsnorm_compiles_at_smollm_width(one_chip):
    txt = _compiled_text(
        lambda x, w: fused_rmsnorm(x, w),
        one_chip, ((8, 128, 960), BF16), ((960,), BF16),
    )
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize(
    "B,S,Hq,Hkv,D",
    [
        (8, 1024, 15, 5, 64),  # smollm-360m: Hkv > 1
        (8, 1024, 4, 1, 256),  # gemma3-1b: MQA
    ],
    ids=["smollm", "gemma3"],
)
def test_flash_decode_compiles(one_chip, B, S, Hq, Hkv, D):
    txt = _compiled_text(
        flash_decode,
        one_chip,
        ((B, Hq, D), BF16), ((B, S, Hkv, D), BF16), ((B, S, Hkv, D), BF16),
        ((B,), jnp.int32),
    )
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize(
    "rows,k,n",
    [
        (128 * 6, 2048, 1408),  # moonlight-16b-a3b b1: gate and up projections
        (32 * 128 * 6, 2048, 1408),  # ... at b32
        (32 * 128 * 6, 1408, 2048),  # ... the down projection at b32
    ],
    ids=["moonlight-up-b1", "moonlight-up-b32", "moonlight-down-b32"],
)
def test_expert_gmm_compiles_at_moonlight_widths(one_chip, rows, k, n):
    from repro.kernels.expert_gmm import expert_gmm

    txt = _compiled_text(
        lambda x, w, g: expert_gmm(x, w, g),
        one_chip, ((rows, k), BF16), ((64, k, n), BF16), ((64,), jnp.int32),
    )
    # one pallas_call a grouped matmul, named for the benchmark's trace reader
    calls = [ln for ln in txt.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1
    assert calls[0].split(" = ")[0].split()[-1].startswith("%expert_gmm.")


@pytest.mark.parametrize("N", [32 * 128, 8 * 128], ids=["moonlight-b32", "moonlight-b8"])
def test_moe_combine_compiles_without_the_padded_copy(one_chip, monkeypatch, N):
    """The local MoE layer at Moonlight's widths combines its expert outputs
    with no f32 copy of them and no (N, k, d) array, and keeps its three
    grouped matmuls."""
    monkeypatch.setattr(ops, "_mode", lambda: "tpu")  # the kernels, as on the chip
    cfg = ARCHS["moonlight-16b-a3b"]
    d, f, E, k = cfg.d_model, cfg.d_ff_expert, cfg.n_experts, cfg.top_k
    S = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    p = {
        "router": {"w": S((d, E), BF16)},
        "score_bias": {"b": S((E,), jnp.float32)},
        "w1": S((E, d, f), BF16),
        "w3": S((E, d, f), BF16),
        "w2": S((E, f, d), BF16),
    }
    txt = (
        jax.jit(lambda p, x: expert_ffn_local(p, cfg, x)[0])
        .lower(p, S((N, d), BF16)).compile().as_text()
    )
    for dt, dims in re.findall(r"\b(\w+)\[(\d+(?:,\d+)*)\]", txt):
        shape = tuple(int(v) for v in dims.split(","))
        assert shape != (N, k, d), (dt, shape)
        assert not (dt == "f32" and len(shape) > 1 and N * k * d == math.prod(shape)), shape
    calls = [ln for ln in txt.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    names = [ln.split(" = ")[0].split()[-1] for ln in calls]
    assert len(names) == 3 and all(n.startswith("%expert_gmm.") for n in names), names

