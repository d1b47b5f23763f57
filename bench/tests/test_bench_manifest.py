"""The manifest keeps the benchmark contract, and new files are found by name."""
import json
import re
import shutil

import pytest

from bench import manifest
from bench.tests.tiny import CELL, REPO, make_root

MAN = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|head|expan|experts_per)")


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "bench/run.py"]
    assert MAN["paths"] == ["bench"]
    assert isinstance(MAN["run_seconds"], int) and 1 <= MAN["run_seconds"] <= 51


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end", "per_layer"])
def test_names_and_units(kind):
    names = [e["name"] for e in MAN[kind]]
    assert len(names) == len(set(names))
    for e in MAN[kind]:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] and "\t" not in e[key]


def test_end_to_end_bounds():
    names = {m["name"] for m in MAN["end_to_end"]}
    assert {"served_rps", "p99_latency_ms", "slo_attainment", "plan_cost", "setup_s"} <= names
    for m in MAN["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_per_layer_metric_has_a_reader():
    moved = {m["name"] for m in MAN["end_to_end"]}
    cells = {w["name"] for w in MAN["workloads"]}
    for m in MAN["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in moved
        assert set(m.get("workloads", cells)) <= cells
        assert callable(manifest.metric_reader(m["name"], REPO / "bench"))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
            kernel = m["name"][: -len("_roofline")]
            assert manifest.kernel(kernel, REPO / "bench").TRACE_NAME == kernel


def test_cells_and_configs():
    configs = {c["name"]: c for c in MAN["configs"]}
    used = set()
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1
        cell = manifest.load_cell(REPO, w["name"])
        assert cell.params["chunk_requests"] > 0
        assert cell.traffic["prompt_tokens"] > 0
        assert cell.per_layer and cell.end_to_end
        used.add(w["config"])
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert used == set(configs)
    for c in configs.values():
        assert c["file"].startswith("bench/configs/")
        assert not any(WIDTH.search(k) for k in c["reduced"])


@pytest.mark.parametrize("config", [c["file"] for c in MAN["configs"]])
def test_config_matches_its_published_sizes(config):
    cfg = json.loads((REPO / config).read_text())
    assert (REPO / "bench" / "references" / f"{cfg['reference']}.py").exists()
    for mod in cfg["modules"]:
        a, p = mod["arch"], mod["published"]
        assert mod["source"].startswith("https://huggingface.co/")
        assert (a["n_layers"], a["d_model"], a["n_heads"], a["n_kv_heads"], a["d_ff"],
                a["vocab_size"], a["tie_embeddings"]) == (
            p["num_hidden_layers"], p["hidden_size"], p["num_attention_heads"],
            p["num_key_value_heads"], p["intermediate_size"], p["vocab_size"],
            p["tie_word_embeddings"])
        assert a["head_dim"] * a["n_heads"] == a["d_model"]
        assert a["param_dtype"] == a["compute_dtype"] == "bfloat16"
        assert 0 < mod["logit_gap_limit"]
        assert "rope_theta" in mod["assumed"]


def test_qwen_names_its_own_source():
    cfg = json.loads((REPO / "bench/configs/smollm360m-qwen15-4b-chain.json").read_text())
    qwen = cfg["modules"][1]
    assert qwen["source"] == "https://huggingface.co/Qwen/Qwen1.5-4B"
    assert qwen["arch"]["source"] == "hf:Qwen/Qwen1.5-4B"


def test_a_new_cell_and_metric_are_new_files(tmp_path):
    """A cell, configuration, traffic mix and metric added as files plus
    manifest entries are found without editing any other file."""
    root = make_root(tmp_path)
    (root / "bench" / "metrics" / "extra.share.py").write_text(
        "def read(run):\n    return 42.0\n"
    )
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["per_layer"].append({"name": "extra.share", "unit": "%", "better": "higher",
                             "source": "program_counter", "layer": "serving loop",
                             "moves": "served_rps", "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    cell = manifest.load_cell(root, CELL)
    assert cell.config["name"] == "tiny-chain"
    assert cell.traffic["process"] == "poisson"
    assert "extra.share" in [m["name"] for m in cell.per_layer]
    assert manifest.metric_reader("extra.share", root / "bench")(None) == 42.0
    # the cells already there are untouched by the addition
    assert "extra.share" not in [m["name"] for m in manifest.load_cell(root, "solo-tight").per_layer]


def test_unknown_cell_is_an_error(tmp_path):
    root = tmp_path / "c"
    root.mkdir()
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    with pytest.raises(KeyError):
        manifest.load_cell(root, "no-such-cell")
