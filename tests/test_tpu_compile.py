"""Main-path Pallas kernels compile for a described TPU v5e at served widths.

Nothing runs: each test lowers and compiles one kernel for one chip of a
``v5e:2x2`` topology description and asserts the compiled program holds the
kernel (``tpu_custom_call``).  This catches block shapes and VMEM use the
chip's compiler refuses, which interpret mode cannot.  The topology is
described only inside the fixture, so collection is identical on every
worker and only the worker that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attention import flash_decode
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import fused_rmsnorm


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
    ],
    ids=["smollm", "gemma3-local"],
)
def test_flash_attention_compiles(one_chip, B, S, Hq, Hkv, D, window):
    txt = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, window=window),
        one_chip,
        ((B, S, Hq, D), BF16), ((B, S, Hkv, D), BF16), ((B, S, Hkv, D), BF16),
    )
    assert "tpu_custom_call" in txt


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
