"""Whole runs of a tiny cell on the CPU with the chip check skipped: a sound
run is correct, and each fault planted in the timed path makes it incorrect.
And the command refuses a machine without a TPU before printing a metric."""
import os
import shutil
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.tests.tiny import CELL, REPO, make_root

KIND = "TPU v5 lite"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


def run(root, **kw):
    return harness.run_cell(root, CELL, seed=2**31 + 77, seconds=0.2, trace=False,
                            t0=time.perf_counter(), require_chip=False, device_kind=KIND, **kw)


def test_sound_run_is_correct(root):
    out = run(root)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    # the end-to-end metrics whose entry applies to the tiny cell (p99 is kept
    # to the cells that list it)
    assert set(out["metrics"]) == {"served_rps", "slo_attainment", "plan_cost", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["checks"]["logit_gap.tiny-b"]["value"] < out["checks"]["logit_gap.tiny-b"]["limit"]


def _patch_executor(monkeypatch, change):
    from repro.launch.serve import ModuleExecutor

    real = ModuleExecutor.__call__

    def call(self, b):
        return change(real(self, b))

    monkeypatch.setattr(ModuleExecutor, "__call__", call)


def test_altered_token_is_caught(monkeypatch, root):
    """One answer altered where it is produced: another token put first."""
    def alter(logits):
        wrong = jnp.argmin(logits[0, 3])
        return logits.at[0, 3, wrong].set(logits[0, 3].max() + 1)

    _patch_executor(monkeypatch, alter)
    out = run(root)
    assert not out["correct"]


def test_half_batch_left_out_is_caught(monkeypatch, root):
    """The forward computes only the first half of the batch's rows."""
    def halve(logits):
        h = max(1, logits.shape[0] // 2)
        return logits.at[h:].set(logits[h:][:, :, ::-1])

    _patch_executor(monkeypatch, halve)
    out = run(root)
    assert not out["correct"]


def test_batch_not_run_on_the_device_is_caught(monkeypatch, root):
    """The service time replays cached step times instead of running every
    batch (``LiveServiceTime(cache=True)``)."""
    from repro.serving import service_time

    real = service_time.LiveServiceTime.__init__

    def cached(self, executors, *, warmup=1, cache=True):
        real(self, executors, warmup=warmup, cache=True)

    monkeypatch.setattr(service_time.LiveServiceTime, "__init__", cached)
    out = run(root)
    assert out["checks"]["unexecuted"]["value"] > 0
    assert not out["correct"]


def test_lost_request_is_caught(monkeypatch, root):
    """A completed request's record goes missing from the result."""
    from repro.serving import ServingEngine

    real = ServingEngine.run

    def lossy(self, *a, **kw):
        res = real(self, *a, **kw)
        p = res.pipeline
        i = int(np.argmax(p.completed))
        p.e2e[i] = float("nan")  # neither completed, shed, dropped nor skipped
        res.e2e_latencies.pop()
        return res

    monkeypatch.setattr(ServingEngine, "run", lossy)
    out = run(root)
    assert out["checks"]["unaccounted"]["value"] > 0
    assert not out["correct"]


def test_traced_run_reads_layer_metrics_without_a_chip_trace(root, tmp_path):
    out = harness.run_cell(root, CELL, seed=5, seconds=0.2, trace=True, t0=time.perf_counter(),
                           require_chip=False, device_kind=KIND, trace_dir=tmp_path / "t")
    assert out["correct"], out["checks"]
    names = set(out["metrics"])
    # the CPU trace holds no TPU plane, so the trace's readers are silent
    assert {"budget_slack", "batch_fill", "executor_share", "mfu"} <= names
    assert not names & {"idle_share", "flash_attention_roofline"}
    assert "breakdown" in out and list(out)[-1] == "checks"


@pytest.mark.parametrize("with_program", [True, False])
def test_command_refuses_a_machine_without_a_tpu(tmp_path, with_program):
    """No TPU: a code other than 0 and no result line, also in a directory
    that holds only BENCHMARK.json and the benchmark's files."""
    if with_program:
        cwd = REPO
    else:
        cwd = tmp_path / "bare"
        shutil.copytree(REPO / "bench", cwd / "bench", ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(REPO / "BENCHMARK.json", cwd / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "solo-tight", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no result" in p.stderr
