"""Analytic profiler: param counts vs published sizes, profile shape sanity."""
import pytest

from repro.configs import ARCHS
from repro.profiling import (
    arch_profile,
    flops_per_token,
    kv_cache_bytes_per_token,
    module_duration,
    param_count,
)
from repro.profiling.hardware import CATALOG, TPU_V4, TPU_V5E, TPU_V5P, spec_for

# published total / active parameter counts (billions)
PUBLISHED = {
    "deepseek-v3-671b": (671, 37),
    "smollm-360m": (0.36, 0.36),
    "jamba-v0.1-52b": (52, 12),
    "gemma-7b": (8.5, 8.5),  # gemma-7b is 8.5B counting embeddings
    "gemma3-1b": (1.0, 1.0),
    "qwen2-moe-a2.7b": (14.3, 2.7),
    "qwen1.5-4b": (3.95, 3.95),
}


@pytest.mark.parametrize("arch,expect", sorted(PUBLISHED.items()))
def test_param_counts_match_published(arch, expect):
    total, active = expect
    n = param_count(ARCHS[arch]) / 1e9
    na = param_count(ARCHS[arch], active=True) / 1e9
    assert n == pytest.approx(total, rel=0.12), n
    assert na == pytest.approx(active, rel=0.15), na


def test_profiles_are_table1_shaped():
    """Throughput increases with batch; duration increases with batch."""
    for arch in ("smollm-360m", "gemma-7b", "qwen2-moe-a2.7b"):
        prof = arch_profile(ARCHS[arch])
        for hw in prof.hardware_names:
            rows = sorted(
                (c for c in prof.configs if c.hardware == hw), key=lambda c: c.batch
            )
            durs = [c.duration for c in rows]
            thr = [c.throughput for c in rows]
            assert all(a <= b + 1e-9 for a, b in zip(durs, durs[1:]))
            assert all(a <= b + 1e-6 for a, b in zip(thr, thr[1:]))


def test_duration_scales_with_model_size():
    small = module_duration(ARCHS["smollm-360m"], 8, 128, TPU_V5E)
    big = module_duration(ARCHS["gemma-7b"], 8, 128, TPU_V5E)
    assert big > 3 * small


def test_faster_hardware_is_faster():
    for arch in ("gemma3-1b", "qwen1.5-4b"):
        d_e = module_duration(ARCHS[arch], 8, 128, CATALOG["tpu-v5e"])
        d_p = module_duration(ARCHS[arch], 8, 128, CATALOG["tpu-v5p"])
        assert d_p < d_e


def test_kv_cache_bytes():
    # deepseek MLA: 576 bytes-ish per token per layer at bf16
    b = kv_cache_bytes_per_token(ARCHS["deepseek-v3-671b"])
    assert b == 61 * (512 + 64) * 2
    # xlstm: no per-token cache at all
    assert kv_cache_bytes_per_token(ARCHS["xlstm-125m"]) == 0.0
    # gemma3 MQA (kv=1) is ~16x lighter per layer than gemma-7b MHA (kv=16)
    assert kv_cache_bytes_per_token(ARCHS["gemma3-1b"]) < 0.07 * kv_cache_bytes_per_token(
        ARCHS["gemma-7b"]
    )


def test_flops_per_token_decode_vs_prefill():
    cfg = ARCHS["qwen1.5-4b"]
    # decode attends the full context, prefill averages ~S/2
    assert flops_per_token(cfg, 32768, decode=True) > flops_per_token(
        cfg, 32768, decode=False
    )


@pytest.mark.parametrize(
    "kind,spec",
    [("TPU v5 lite", TPU_V5E), ("TPU v4", TPU_V4), ("TPU v5", TPU_V5P)],
)
def test_spec_for_device_kind(kind, spec):
    """The peaks table is keyed by the device_kind JAX reports."""

    class Dev:
        device_kind = kind

    assert spec_for(kind) is spec
    assert spec_for(Dev()) is spec


@pytest.mark.parametrize("kind", ["cpu", "TPU v6 lite", ""])
def test_spec_for_unknown_kind_raises(kind):
    with pytest.raises(KeyError, match="no peaks"):
        spec_for(kind)
