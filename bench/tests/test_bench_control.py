"""The logit-gap comparison tells the served bf16 forward from its control.

At SmolLM-360M's published widths, cut to two layers and two rows so that a
CPU test run holds it: the program's executor passes the configuration's
limit, and the float8 control (the reference computed with e4m3 operands,
the precision below the configured bf16) fails it.  On the chip the same
readings at the cells' own sizes come from ``bench/control.py``.
"""
import json

import jax
import pytest

from bench import manifest, weights
from bench.harness import _arch_config
from bench.tests.tiny import REPO

CFG = json.loads((REPO / "bench/configs/smollm-360m.json").read_text())
MOD = CFG["modules"][0]
REF = manifest.reference(CFG["reference"], REPO / "bench")


@pytest.fixture(scope="module")
def served():
    from repro.launch.serve import ModuleExecutor

    arch = dict(MOD["arch"], n_layers=2)
    ex = ModuleExecutor(_arch_config(arch), seq=128)
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), ex.params)
    ex.params = weights.make_params(shapes, 2**33 + 1, 0)
    ex(2)
    toks = weights.make_tokens(2**33 + 1, 0, 2, 128, arch["vocab_size"])
    ex._tokens[2] = toks
    return arch, ex.params, toks, ex(2)


def test_program_passes_and_control_fails(served):
    arch, params, toks, out = served
    eps, limit = MOD["rms_norm_eps"], MOD["logit_gap_limit"]
    gap, agree = REF.widest_gap(params, toks, out, arch, eps)
    control, c_agree = REF.widest_gap(params, toks, None, arch, eps, quant=REF.fp8)
    assert gap < limit < control
    assert agree > c_agree


def test_reference_against_itself_reads_zero(served):
    arch, params, toks, _ = served
    ref = REF.forward(params, toks, arch, MOD["rms_norm_eps"])
    gap, agree = REF.widest_gap(params, toks, ref, arch, MOD["rms_norm_eps"])
    assert gap == 0.0 and agree == 1.0
