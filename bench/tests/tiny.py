"""A checkout-shaped copy of the benchmark with one tiny cell added, for CPU
tests that drive a whole run without the chip."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
CELL = "tiny-cell"


def make_root(tmp: Path) -> Path:
    """Copy ``BENCHMARK.json`` and ``bench/`` under ``tmp`` and add the tiny
    configuration, traffic mix and cell as new files and manifest entries."""
    root = Path(tmp) / "checkout"
    shutil.copytree(REPO / "bench", root / "bench", ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(DATA / "tiny-chain.json", root / "bench" / "configs" / "tiny-chain.json")
    shutil.copy(DATA / "tiny-poisson.json", root / "bench" / "traffic" / "tiny-poisson.json")
    shutil.copy(DATA / "tiny-cell.json", root / "bench" / "workloads" / f"{CELL}.json")
    man = json.loads((REPO / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "tiny-chain", "source": "test", "file": "bench/configs/tiny-chain.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": CELL, "config": "tiny-chain", "traffic": "tiny-poisson",
                             "chips": 1, "why": "test"})
    for m in man["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root
