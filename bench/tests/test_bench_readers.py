"""Each per-layer reader on a synthetic run record, against a hand count."""
import json
from types import SimpleNamespace

import pytest

from bench import flops, manifest
from bench.peaks import peaks_for
from bench.roofline import kernel_roofline
from bench.trace_reduce import Op, Summary
from bench.tests.tiny import REPO

MAN = json.loads((REPO / "BENCHMARK.json").read_text())
CHAIN = json.loads((REPO / "bench/configs/smollm360m-qwen15-4b-chain.json").read_text())
ARCHS = {m["name"]: m["arch"] for m in CHAIN["modules"]}
PK = peaks_for("TPU v5 lite")
FA = manifest.kernel("flash_attention", REPO / "bench")
RMS = manifest.kernel("fused_rmsnorm", REPO / "bench")


def read(name, run):
    return manifest.metric_reader(name, REPO / "bench")(run)


def record(**kw):
    base = dict(
        cell=SimpleNamespace(bench_dir=REPO / "bench"),
        archs=ARCHS,
        seq=128,
        plan=SimpleNamespace(workload=SimpleNamespace(slo=1.0), e2e_latency=0.25),
        measured={("smollm-360m", 32): [0.04, 0.04], ("qwen1.5-4b", 32): [0.2]},
        chunk_s=[0.5, 0.5],
        window_s=1.0,
        peaks=PK,
        fill=None,
        trace=None,
        traced_forwards={},
    )
    base.update(kw)
    return SimpleNamespace(**base)


def ops(name, n, dur_ns, start=0.0):
    return [Op(f"{name}.{i % 3}", start + i * dur_ns, dur_ns) for i in range(n)]


def test_budget_slack():
    assert read("budget_slack", record()) == pytest.approx(75.0)


def test_batch_fill():
    assert read("batch_fill", record()) is None
    run = record(fill={"members": 90, "phantoms": 10, "slots": 100})
    assert read("batch_fill", run) == pytest.approx(80.0)


def test_executor_share():
    assert read("executor_share", record()) == pytest.approx(28.0)


def test_mfu():
    want = (2 * flops.forward_flops(ARCHS["smollm-360m"], 32, 128)
            + flops.forward_flops(ARCHS["qwen1.5-4b"], 32, 128)) / PK["flops_bf16"]
    assert read("mfu", record()) == pytest.approx(100 * want)
    assert read("mfu", record(measured={})) is None


def test_idle_share():
    assert read("idle_share", record()) is None
    run = record(trace=Summary(window_s=2.0, busy_s=1.5))
    assert read("idle_share", run) == pytest.approx(25.0)


@pytest.mark.parametrize("kernel,name,per_fwd", [
    (FA, "flash_attention", 32), (RMS, "fused_rmsnorm", 65)])
def test_roofline(kernel, name, per_fwd):
    fwd = {("smollm-360m", 4): 3}
    calls = kernel.calls(ARCHS["smollm-360m"], 4, 128)
    c_ops, c_bytes = kernel.cost(**calls[0])
    least = max(c_ops / PK["flops_bf16"], c_bytes / PK["hbm_bytes_per_s"])
    dur_ns = 2 * least * 1e9  # every call at half its roofline
    trace = Summary(window_s=1.0, busy_s=0.5, ops=ops(kernel.TRACE_NAME, 3 * per_fwd, dur_ns))
    run = record(trace=trace, traced_forwards=fwd)
    assert kernel_roofline(run, name) == pytest.approx(50.0)
    # one call too few in the trace: nothing sound to read
    trace.ops.pop()
    assert kernel_roofline(run, name) is None
    # a trace in which the kernel does not appear
    assert kernel_roofline(record(trace=Summary(1.0, 0.5, ops("other", 4, 1e3)),
                                  traced_forwards=fwd), name) is None


def test_flash_attention_roofline_reader():
    fwd = {("qwen1.5-4b", 32): 2}
    calls = FA.calls(ARCHS["qwen1.5-4b"], 32, 128)
    least = max(FA.cost(**calls[0])[0] / PK["flops_bf16"], FA.cost(**calls[0])[1] / PK["hbm_bytes_per_s"])
    trace = Summary(window_s=1.0, busy_s=0.9, ops=ops("flash_attention", 80, 4 * least * 1e9))
    assert read("flash_attention_roofline", record(trace=trace, traced_forwards=fwd)) == pytest.approx(25.0)


def test_every_manifest_metric_reads_none_or_a_number_on_an_empty_record():
    for m in MAN["per_layer"]:
        v = read(m["name"], record(measured={}, fill=None, trace=None))
        assert v is None or isinstance(v, float)
