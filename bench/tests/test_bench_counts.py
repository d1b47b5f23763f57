"""The benchmark's FLOP count and kernel operations/bytes, against hand counts
at the served shapes."""
import json

import pytest

from bench import flops, manifest
from bench.peaks import peaks_for
from bench.tests.tiny import REPO

CHAIN = json.loads((REPO / "bench/configs/smollm360m-qwen15-4b-chain.json").read_text())
SMOL, QWEN = (m["arch"] for m in CHAIN["modules"])
FA = manifest.kernel("flash_attention", REPO / "bench")
RMS = manifest.kernel("fused_rmsnorm", REPO / "bench")


def test_qwen_b32_forward_is_29_3_tflop():
    # per layer: q,k,v,o 4 * 2560^2 and the MLP 3 * 2560 * 6912 multiply-adds
    # per token; attention 2 contractions * 20 heads * 128 * (128*129/2) pairs
    # per row; unembedding 2560 * 151936 per token
    per_layer = 2 * (4 * 2560 * 2560 + 3 * 2560 * 6912) * 4096 + 4 * 32 * 20 * 128 * 8256
    total = 40 * per_layer + 2 * 2560 * 151936 * 4096
    assert flops.forward_flops(QWEN, 32, 128) == total
    assert round(total / 1e12, 1) == 29.3


def test_smollm_forward_hand_count():
    # q and o 960 x 960, k and v 960 x (5 * 64), MLP 3 * 960 * 2560
    mm = 960 * 960 * 2 + 960 * 320 * 2 + 3 * 960 * 2560
    assert flops.layer_matmul_params(SMOL) == mm == 9_830_400
    per_layer = 2 * mm * 32 * 128 + 4 * 32 * 15 * 64 * 8256
    assert flops.forward_flops(SMOL, 32, 128) == 32 * per_layer + 2 * 960 * 49152 * 32 * 128


@pytest.mark.parametrize(
    "arch,b,ops,nbytes",
    [
        # 2 contractions x 2 ops x B x Hq x D x pairs; q, o at Hq heads and
        # k, v at Hkv heads, bf16, each moved once
        (SMOL, 32, 4 * 32 * 15 * 64 * 8256, 32 * 128 * 64 * (2 * 15 + 2 * 5) * 2),
        (QWEN, 32, 4 * 32 * 20 * 128 * 8256, 32 * 128 * 128 * (2 * 20 + 2 * 20) * 2),
        (SMOL, 1, 4 * 1 * 15 * 64 * 8256, 128 * 64 * 40 * 2),
    ],
)
def test_flash_attention_cost(arch, b, ops, nbytes):
    calls = FA.calls(arch, b, 128)
    assert len(calls) == arch["n_layers"]
    assert FA.cost(**calls[0]) == (ops, nbytes)


@pytest.mark.parametrize("arch,width", [(SMOL, 960), (QWEN, 2560)])
def test_fused_rmsnorm_cost(arch, width):
    calls = RMS.calls(arch, 8, 128)
    assert len(calls) == 2 * arch["n_layers"] + 1
    rows = 8 * 128
    assert RMS.cost(**calls[0]) == (4 * rows * width, (2 * rows * width + width) * 2)


def test_flash_attention_at_smollm_b32_is_bandwidth_bound():
    pk = peaks_for("TPU v5 lite")
    ops, nbytes = FA.cost(**FA.calls(SMOL, 32, 128)[0])
    assert nbytes / pk["hbm_bytes_per_s"] > ops / pk["flops_bf16"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("cpu")
