"""Find a cell's files by the names in ``BENCHMARK.json``.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name: each is a file found from the manifest, so that a later change adds a
file and an entry and edits nothing.

* configuration ``<name>``: the ``file`` its manifest entry gives
  (``bench/configs/<name>.json``), with its plain reference
  ``bench/references/<reference>.py``;
* traffic mix ``<traffic>``: ``bench/traffic/<traffic>.json``;
* cell ``<name>``: ``bench/workloads/<name>.json`` (how the window is cut);
* per-layer metric ``<name>``: ``bench/metrics/<name>.py``, whose
  ``read(run)`` returns a number or None.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType


@dataclass
class Cell:
    name: str
    chips: int
    config: dict   # the configuration file
    traffic: dict  # the traffic mix file
    params: dict   # the cell's own file
    end_to_end: list[dict]
    per_layer: list[dict]
    bench_dir: Path  # where the cell's metric readers and reference live


def load_manifest(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _read_json(path: Path) -> dict:
    return json.loads(path.read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    root = Path(root)
    bench_dir = root / "bench"
    man = load_manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in man["configs"]}
    e2e = [m for m in man["end_to_end"] if _applies(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in man["per_layer"] if _applies(m, name) and m["moves"] in moved]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=_read_json(root / configs[w["config"]]["file"]),
        traffic=_read_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        params=_read_json(bench_dir / "workloads" / f"{name}.json"),
        end_to_end=e2e,
        per_layer=per_layer,
        bench_dir=bench_dir,
    )


def _load_module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: Path):
    """The ``read(run)`` function of per-layer metric ``name``."""
    return _load_module(bench_dir / "metrics" / f"{name}.py", f"bench_metric_{name}").read


def reference(name: str, bench_dir: Path) -> ModuleType:
    return _load_module(bench_dir / "references" / f"{name}.py", f"bench_reference_{name}")


def kernel(name: str, bench_dir: Path) -> ModuleType:
    return _load_module(bench_dir / "kernels" / f"{name}.py", f"bench_kernel_{name}")
