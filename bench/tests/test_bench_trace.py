"""The trace reduction on a small trace recorded on a TPU v5e: one forward of
smollm-360m at batch 1, seq 128, through the served executor."""
import pytest

from bench import trace_reduce
from bench.tests.tiny import DATA

TRACE = DATA / "smollm-b1.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce_file(TRACE, window_s=0.01)


def test_names():
    op = "%flash_attention.6 = bf16[15,128,64]{2,1,0} custom-call(bf16[15,128,64]{2,1,0} %bitcast.1)"
    assert trace_reduce.own_name(op) == "flash_attention.6"
    assert trace_reduce.kind_of("flash_attention.6") == "flash_attention"
    assert trace_reduce.kind_of("fused_rmsnorm") == "fused_rmsnorm"
    assert trace_reduce.kind_of("copy-start") == "copy-start"


def test_one_device_with_busy_time(summary):
    assert summary.n_devices == 1
    # the forward's ops: a few milliseconds, inside the traced window
    assert 1e-3 < summary.busy_s < 5e-3
    assert summary.window_s == 0.01


def test_kernels_once_per_call(summary):
    # one smollm forward: one attention per layer; two norms per layer and
    # the final one
    seconds, calls = summary.kernel("flash_attention")
    assert calls == 32 and 0 < seconds < summary.busy_s
    seconds, calls = summary.kernel("fused_rmsnorm")
    assert calls == 65 and 0 < seconds < summary.busy_s
    assert summary.kernel("no_such_kernel") == (0.0, 0)


def test_breakdown(summary):
    b = summary.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    names = [n for n, _ in b["device_ops"]]
    assert "flash_attention.6" in names
    # the scan's loop holds the other ops and is not counted beside them
    assert not any(trace_reduce.kind_of(n) == "while" for n in names)
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_missing_trace_dir_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace_reduce.reduce_dir(tmp_path, window_s=1.0)
