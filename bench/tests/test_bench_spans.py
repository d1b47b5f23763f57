"""The readers of the program's own spans and wait counters: finite on a
tiny traced run on the CPU, and silent on rows that lack their counters (a
program without the spans)."""
import math
import time
from types import SimpleNamespace

import pytest

from bench import harness, manifest
from bench.tests.tiny import CELL, REPO, make_root

KIND = "TPU v5 lite"
NEW = ("collect_wait_ms", "queue_wait_ms", "dispatch_share", "sync_share", "gc_share")


def read(name, run):
    return manifest.metric_reader(name, REPO / "bench")(run)


def record(rows_per_chunk, window_s=2.0):
    """A run record whose chunks' snapshots hold the given row lists."""
    results = [SimpleNamespace(metrics=SimpleNamespace(rows=r)) for r in rows_per_chunk]
    return SimpleNamespace(results=results, window_s=window_s)


ROWS = [
    {"module": "a", "collect_s": 0.3, "queue_s": 0.1, "waited": 100,
     "dispatch_s": 0.2, "sync_s": 0.6},
    {"module": "b", "collect_s": 0.1, "queue_s": 0.3, "waited": 100,
     "dispatch_s": 0.1, "sync_s": 0.4},
    {"module": "(host)", "gc_s": 0.05, "gc_n": 3},
]


@pytest.mark.parametrize("name,want", [
    ("collect_wait_ms", 2.0), ("queue_wait_ms", 2.0), ("dispatch_share", 15.0),
    ("sync_share", 50.0), ("gc_share", 2.5),
])
def test_reader_sums_each_row_list_once(name, want):
    # every chunk's snapshot holds the registry's one row list
    assert read(name, record([ROWS, ROWS, ROWS])) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_reader_is_silent_without_its_counter(name):
    older = [{"module": "a", "batches": 4, "occupancy": 1.0, "closes": {"full": 4}}]
    assert read(name, record([older, older])) is None
    assert read(name, record([])) is None


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("bench"))
    return harness.run_cell(root, CELL, seed=2**31 + 5, seconds=0.2, trace=True,
                            t0=time.perf_counter(), require_chip=False, device_kind=KIND,
                            trace_dir=tmp_path_factory.mktemp("trace"))


@pytest.mark.parametrize("name", NEW)
def test_tiny_traced_run_reads_each_new_metric(traced, name):
    assert traced["correct"], traced["checks"]
    v = traced["metrics"][name]["value"]
    assert math.isfinite(v) and v >= 0.0


def test_spans_nest_inside_the_executor_calls(traced):
    m = {k: v["value"] for k, v in traced["metrics"].items()}
    assert 0.0 < m["dispatch_share"] + m["sync_share"] <= m["executor_share"]
