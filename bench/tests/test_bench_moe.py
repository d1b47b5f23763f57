"""The latent-attention MoE configuration against its plain reference.

On the CPU, in float32, at Moonlight-16B-A3B's layout cut to a few narrow
layers: the program's routes equal the reference's own, its logits agree
to float32 rounding, and each routing fault planted in the program fails the
reference's check.  Then whole runs of a tiny MoE cell through the harness,
whose kept outputs are ``(logits, routes)``.
"""
import dataclasses
import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops_moe, harness, manifest, weights
from bench.tests.tiny import DATA, REPO

REF = manifest.reference("mla_moe_lm", REPO / "bench")
SEED = 2**33 + 5
EPS = 1e-6
TIGHT = 1e-4  # float32 program against the float32 reference: rounding only


def _smoke():
    from repro.configs import get_config

    return get_config("moonlight-16b-a3b", smoke=True)


@pytest.fixture(scope="module")
def smoke():
    """The SMOKE config's program, with the benchmark's seeded weights."""
    from repro.launch.serve import ModuleExecutor

    cfg = _smoke()
    ex = ModuleExecutor(cfg, seq=16)
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), ex.params)
    ex.params = weights.make_params(shapes, SEED, 0)
    return cfg, dataclasses.asdict(cfg), ex


def _tokens(cfg, b):
    return weights.make_tokens(SEED, 0, b, 16, cfg.vocab_size)


def _served(ex, b):
    ex(b)
    ex._tokens[b] = _tokens(ex.cfg, b)
    return ex(b)


@pytest.mark.parametrize("b", [1, 4])
def test_program_matches_reference(smoke, b):
    cfg, arch, ex = smoke
    logits, routes = _served(ex, b)
    ref, own, shortfall, differ = REF.forward(ex.params, _tokens(cfg, b), arch, EPS)
    assert routes.shape == (cfg.n_layers - cfg.n_dense_layers, b * 16, cfg.top_k)
    assert bool(jnp.all(routes == own)) and float(shortfall) == 0.0 and int(differ) == 0
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref), rtol=TIGHT, atol=TIGHT)
    gap, agree = REF.widest_gap(ex.params, _tokens(cfg, b), (logits, routes), arch, EPS)
    assert gap < TIGHT and agree == 1.0


def _faulty_route(fault):
    """``moe.route`` for sigmoid_noaux routing with one fault planted."""
    def route(p, cfg, x):
        z = jnp.dot(x, p["router"]["w"], preferred_element_type=jnp.float32)[:, :cfg.n_experts]
        s, bias = jax.nn.sigmoid(z), p["score_bias"]["b"][:cfg.n_experts]
        _, ids = jax.lax.top_k(s if fault == "bias left out of selection" else s + bias, cfg.top_k)
        g = jnp.take_along_axis(s + bias if fault == "bias in the gate weights" else s, ids, -1)
        if fault != "no renormalisation":
            g = g / g.sum(-1, keepdims=True)
        return ids, g * cfg.routed_scale, jnp.zeros((), jnp.float32)
    return route


FAULTS = ["softmax scoring", "bias in the gate weights", "bias left out of selection",
          "no renormalisation", "no routed scale", "shared experts dropped",
          "routes from the wrong layer"]


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_routing_fault_is_caught(smoke, monkeypatch, fault):
    from repro.models import Model
    from repro.models import moe

    cfg, arch, ex = smoke
    toks = _tokens(cfg, 4)
    params = ex.params
    if fault == "softmax scoring":
        cfg = cfg.replace(router_score="softmax")
    elif fault in ("bias in the gate weights", "bias left out of selection", "no renormalisation"):
        monkeypatch.setattr(moe, "route", _faulty_route(fault))
    elif fault == "no routed scale":
        cfg = cfg.replace(routed_scale=1.0)
    elif fault == "shared experts dropped":
        seg = params["segments"][1]
        ffn = {k: v for k, v in seg[0]["ffn"].items() if k != "shared"}
        params = dict(params, segments=[params["segments"][0], ({**seg[0], "ffn": ffn},)])
    out = Model(cfg).forward(params, toks)
    routes = out.routes
    if fault == "routes from the wrong layer":
        routes = jnp.roll(routes, 1, axis=0)
    gap, _ = REF.widest_gap(ex.params, toks, (out.logits, routes), arch, EPS)
    assert gap > 1e-2, gap


def test_control_is_far_from_the_program(smoke):
    """The float8 control, on the reference's own routes, lies well outside
    the float32 program's gap."""
    cfg, arch, ex = smoke
    gap, _ = REF.widest_gap(ex.params, _tokens(cfg, 4), None, arch, EPS, quant=REF.fp8)
    assert gap > 100 * TIGHT


def test_make_params_draws_every_leaf_of_the_moe_tree(smoke):
    cfg, _, ex = smoke
    leaves = jax.tree_util.tree_leaves_with_path(ex.params)
    assert all(bool(jnp.all(jnp.isfinite(v))) and float(jnp.std(v)) > 0 for _, v in leaves)
    bias = ex.params["segments"][1][0]["ffn"]["score_bias"]["b"]
    assert bias.shape == (cfg.n_layers - cfg.n_dense_layers, cfg.n_experts)
    assert bias.dtype == jnp.float32
    assert 0.05 < float(jnp.std(bias)) < 0.2  # drawn as a bias: 0.1 N(0, 1)


def test_moonlight_b32_forward_is_8_9_tflop():
    cfg = json.loads((REPO / "bench/configs/moonlight-16b-a3b.json").read_text())
    a = cfg["modules"][0]["arch"]
    # per token: MLA q 2048 x 16*192, kv_a 2048 x 576, k_b and v_b 512 x 2048,
    # o 2048 x 2048; the dense MLP 3 x 2048 x 11264; per expert layer the
    # router 2048 x 64 and 6 routed + 2 shared experts of 3 x 2048 x 1408;
    # attention 16 heads x (192 + 128) over 128*129/2 pairs per row
    mla = 2048 * 3072 + 2048 * 576 + 2 * 512 * 2048 + 2048 * 2048
    assert flops_moe.mla_params(a) == mla
    t = 32 * 128
    total = 9 * (2 * mla * t + 2 * 32 * 16 * 320 * 8256) + 2 * 3 * 2048 * 11264 * t
    total += 8 * 2 * (2048 * 64 + 8 * 3 * 2048 * 1408) * t + 2 * 2048 * 163840 * t
    assert flops_moe.forward_flops(a, 32, 128) == total
    assert round(total / 1e12, 1) == 8.9


def test_expert_gmm_cost():
    k = manifest.kernel("expert_gmm", REPO / "bench")
    cfg = json.loads((REPO / "bench/configs/moonlight-16b-a3b.json").read_text())
    calls = k.calls(cfg["modules"][0]["arch"], 32, 128)
    assert len(calls) == 3 * 8
    rows = 32 * 128 * 6
    assert k.cost(**calls[0]) == (2 * rows * 2048 * 1408, (64 * 2048 * 1408 + rows * (2048 + 1408)) * 2)
    assert k.calls({"n_layers": 2, "d_model": 64}, 1, 128) == []


# ------------------------------------------------------------ the harness
CELL = "tiny-moe-cell"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout-shaped copy of the benchmark with a tiny MoE cell added."""
    root = tmp_path_factory.mktemp("bench") / "checkout"
    shutil.copytree(REPO / "bench", root / "bench", ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(DATA / "tiny-moe.json", root / "bench" / "configs" / "tiny-moe.json")
    shutil.copy(DATA / "tiny-poisson.json", root / "bench" / "traffic" / "tiny-poisson.json")
    shutil.copy(DATA / "tiny-cell.json", root / "bench" / "workloads" / f"{CELL}.json")
    man = json.loads((REPO / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "tiny-moe", "source": "test", "file": "bench/configs/tiny-moe.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": CELL, "config": "tiny-moe", "traffic": "tiny-poisson",
                             "chips": 1, "why": "test"})
    for m in man["per_layer"]:
        if "moe-relaxed" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


def _run(root, **kw):
    import time

    return harness.run_cell(root, CELL, seed=2**31 + 77, seconds=0.2, t0=time.perf_counter(),
                            require_chip=False, device_kind="TPU v5 lite", **kw)


def test_tiny_moe_cell_is_correct(root):
    out = _run(root, trace=False)
    assert out["correct"], out["checks"]
    assert out["checks"]["logit_gap.tiny-moe"]["value"] < 1e-4


def test_tiny_moe_cell_catches_routes_from_the_wrong_layer(root, monkeypatch):
    from repro.launch.serve import ModuleExecutor

    real = ModuleExecutor.__call__
    monkeypatch.setattr(ModuleExecutor, "__call__",
                        lambda self, b: (lambda o: (o[0], jnp.roll(o[1], 1, 0)))(real(self, b)))
    out = _run(root, trace=False)
    assert out["checks"]["logit_gap.tiny-moe"]["value"] == 1e30
    assert not out["correct"]


def test_tiny_moe_cell_traced_reads_moe_mfu(root, tmp_path):
    out = _run(root, trace=True, trace_dir=tmp_path / "t")
    assert out["correct"], out["checks"]
    assert "moe_mfu" in out["metrics"] and out["metrics"]["moe_mfu"]["value"] > 0
    # the CPU trace holds no TPU plane: the kernel's share is silent
    assert "expert_gmm_roofline" not in out["metrics"]
