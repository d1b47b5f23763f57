"""Serving observability layer (ISSUE-7): structured tracing, the metrics
registry, and SLO-miss forensics.

Pins the layer's load-bearing properties: results are BIT-identical with
observability on, off, or sampled (fast path, event loop, and
control-plane paths); every miss report conserves — cause counts sum exactly to
``offered - completed-in-SLO`` — across apps x arrivals x admission x
control epochs, with each miss carrying exactly one cause; the Perfetto
export is valid trace-event JSON; the trace ring buffer and deterministic
sampling behave as documented; the `relax` chain on/off is
bit-identical under burst deadlines (the PR-6 inertness finding the
rename records); and the BENCH_serving.json writer merges by name into a
deterministic, schema-versioned document.
"""
import gc
import json
import os
import sys

import numpy as np
import pytest

from repro.core import Planner
from repro.core import baselines as B
from repro.serving import (
    MISS_CAUSES,
    ControlLoopConfig,
    FrontendConfig,
    ObservabilityConfig,
    QueueDepth,
    ServingEngine,
    TokenBucket,
    TraceRecorder,
)
from repro.serving.arrivals import trace_arrivals
from repro.serving.pipeline import PipelineConfig
from repro.workloads import synth_profiles
from repro.workloads.apps import app_by_name, make_workload

PROFILES = synth_profiles()

_PLANS: dict = {}


def suite_plan(name, rate, slo):
    key = (name, rate, slo)
    if key not in _PLANS:
        plan = Planner(B.HARPAGON).plan(
            make_workload(app_by_name(name), rate, slo), PROFILES
        )
        assert plan.feasible
        _PLANS[key] = plan
    return _PLANS[key]


def result_key(res):
    """Everything a run computes, hashable — the bit-exactness fingerprint."""
    key = [
        tuple(res.e2e_latencies), res.shed, res.dropped, res.attempts,
        tuple(sorted(
            (m, s.batches, s.dropped, s.phantom, tuple(s.latencies))
            for m, s in res.module_stats.items()
        )),
    ]
    if res.pipeline is not None:
        pr = res.pipeline
        key.append(pr.e2e.tobytes())
        key.extend(pr.finish[m].tobytes() for m in pr.modules)
        key.append(pr.shed.tobytes())
        key.append(pr.dropped.tobytes())
    return tuple(key)


ADMISSIONS = {
    "none": None,
    "token_bucket": TokenBucket(burst=4),
    "queue_depth": QueueDepth(depth=8),
}


# ------------------------------------------------ bit-exactness, all paths


class TestBitExact:
    def test_pipeline_on_off_sampled(self):
        plan = suite_plan("face", 150.0, 2.5)
        eng = ServingEngine(plan)
        kw = dict(
            arrivals="mmpp", seed=0, offered_rate=1.3 * 150.0,
            frontend=FrontendConfig(admission=TokenBucket(burst=4)),
            pipeline=True,
        )
        off = eng.run(800, 150.0, **kw)
        on = eng.run(800, 150.0, observability=True, **kw)
        sampled = eng.run(
            800, 150.0,
            observability=ObservabilityConfig(sample=0.1, capacity=512), **kw
        )
        assert result_key(off) == result_key(on) == result_key(sampled)
        assert off.metrics is None and off.trace is None
        assert on.metrics is not None and on.trace is not None

    def test_flat_on_off(self):
        plan = suite_plan("face", 150.0, 2.5)
        eng = ServingEngine(plan)
        kw = dict(
            arrivals="mmpp", seed=0, offered_rate=1.3 * 150.0,
            frontend=FrontendConfig(admission=QueueDepth(depth=8)),
        )
        off = eng.run(800, 150.0, **kw)
        on = eng.run(800, 150.0, observability=True, **kw)
        assert result_key(off) == result_key(on)
        # queue-depth ingress sheds reach the telemetry (admission.obs hook)
        assert on.shed > 0
        assert sum(
            1 for ev in on.trace.events() if ev[4] == "shed"
        ) == on.shed

    def test_control_plane_on_off(self):
        plan = suite_plan("face", 150.0, 2.5)
        eng = ServingEngine(plan)
        n, rate = 1200, 150.0
        period = n / rate
        arr = trace_arrivals(n, rate, seed=0, period=period)
        kw = dict(
            arrivals=arr, timeout="budget",
            frontend=FrontendConfig(dummies=True, burst_deadline=True),
            pipeline=True,
            control=ControlLoopConfig(
                interval=period / 4, profiles=PROFILES, margin=0.25
            ),
        )
        off = eng.run(n, rate, **kw)
        on = eng.run(n, rate, observability=True, **kw)
        assert result_key(off) == result_key(on)
        # one metrics window per epoch boundary + the final flush
        assert on.metrics is not None and len(on.metrics.rows) > 0

    def test_fastpath_reports_column_metrics(self):
        # a plain open-loop run stays fast-path eligible with tracing on:
        # the telemetry is column-level (bulk batch/busy tallies), not
        # per-event spans, and results remain bit-exact
        plan = suite_plan("traffic", 100.0, 2.0)
        eng = ServingEngine(plan)
        off = eng.run(2000, 100.0, pipeline=True)
        on = eng.run(2000, 100.0, pipeline=True, observability=True)
        assert result_key(off) == result_key(on)
        rows = on.metrics.rows
        assert rows and sum(r["batches"] for r in rows) == sum(
            s.batches for s in on.module_stats.values()
        )


# ----------------------------------------- miss-cause conservation matrix


class TestConservation:
    @pytest.mark.parametrize("app,rate,slo", [
        ("face", 150.0, 2.5), ("traffic", 100.0, 2.0),
    ])
    @pytest.mark.parametrize("arrivals", ["uniform", "mmpp"])
    @pytest.mark.parametrize("admission", list(ADMISSIONS))
    @pytest.mark.parametrize("control", [False, True])
    def test_conserves(self, app, rate, slo, arrivals, admission, control):
        plan = suite_plan(app, rate, slo)
        eng = ServingEngine(plan)
        n = 400
        ctrl = (
            ControlLoopConfig(interval=n / rate / 3, profiles=PROFILES)
            if control
            else None
        )
        res = eng.run(
            n, rate, arrivals=arrivals, seed=0, timeout="budget",
            frontend=FrontendConfig(
                dummies=True, admission=ADMISSIONS[admission]
            ),
            offered_rate=1.3 * rate, pipeline=True, control=ctrl,
        )
        rep = res.miss_report()
        assert rep.conserved
        assert set(rep.counts) <= set(MISS_CAUSES)
        # exactly one cause per miss, no cause on non-misses
        n_caused = int((rep.cause_of >= 0).sum())
        assert n_caused == rep.total == sum(rep.counts.values())
        assert rep.offered - rep.completed_in_slo == rep.total

    def test_shed_frames_are_admission_shed(self):
        plan = suite_plan("face", 150.0, 2.5)
        res = ServingEngine(plan).run(
            600, 150.0, arrivals="mmpp", seed=0,
            frontend=FrontendConfig(admission=TokenBucket(burst=4)),
            offered_rate=1.5 * 150.0, pipeline=True,
        )
        rep = res.miss_report()
        n_shed = int(res.pipeline.shed.sum())
        assert n_shed > 0
        assert rep.counts.get("admission_shed", 0) == n_shed
        assert rep.conserved


# ------------------------------------------------ trace recorder mechanics


class TestTraceRecorder:
    def test_ring_buffer_overwrites_and_counts_drops(self):
        tr = TraceRecorder(capacity=4)
        for i in range(10):
            tr.instant(float(i), "m", 0, f"e{i}")
        evs = tr.events()
        assert len(evs) == 4
        assert [e[1] for e in evs] == [6.0, 7.0, 8.0, 9.0]  # oldest evicted
        assert tr.dropped == 6

    def test_deterministic_stride_sampling(self):
        tr = TraceRecorder(sample=0.5)
        hits = [tr.sampled() for _ in range(10)]
        assert hits == [True, False] * 5
        assert TraceRecorder(sample=1.0).stride == 1
        assert TraceRecorder(sample=0.1).stride == 10

    def test_validation(self):
        with pytest.raises(ValueError):
            TraceRecorder(capacity=0)
        with pytest.raises(ValueError):
            TraceRecorder(sample=0.0)
        with pytest.raises(ValueError):
            ObservabilityConfig(sample=2.0)

    def test_chrome_export_is_loadable(self, tmp_path):
        plan = suite_plan("face", 150.0, 2.5)
        n, rate = 900, 150.0
        period = n / rate
        res = ServingEngine(plan).run(
            n, rate,
            arrivals=trace_arrivals(n, rate, seed=0, period=period),
            timeout="budget",
            frontend=FrontendConfig(dummies=True, burst_deadline=True),
            pipeline=True,
            control=ControlLoopConfig(
                interval=period / 3, profiles=PROFILES, margin=0.25
            ),
            observability=True,
        )
        path = res.trace.export(str(tmp_path / "trace.json"))
        doc = json.loads(open(path).read())
        evs = doc["traceEvents"]
        assert evs
        assert doc["displayTimeUnit"] == "ms"
        phs = {e["ph"] for e in evs}
        assert phs <= {"X", "i", "C", "M"}
        assert "X" in phs and "M" in phs  # spans + process metadata
        for e in evs:
            assert isinstance(e["pid"], int) and isinstance(e["name"], str)
            if e["ph"] == "X":
                assert e["dur"] >= 0 and e["ts"] >= 0
        # an epoch instant per control epoch (always recorded, never sampled)
        n_epoch = sum(1 for e in evs if e["ph"] == "i" and e["name"] == "epoch")
        assert n_epoch == len(res.epochs) - 1  # history[0] predates the loop


# --------------------------------------------------------- metrics sanity


class TestMetrics:
    def test_rows_are_sane(self):
        plan = suite_plan("face", 150.0, 2.5)
        res = ServingEngine(plan).run(
            800, 150.0, arrivals="mmpp", seed=0, timeout="budget",
            frontend=FrontendConfig(
                dummies=True, admission=TokenBucket(burst=4)
            ),
            offered_rate=1.3 * 150.0, pipeline=True, observability=True,
        )
        # module rows: not the ingress sheds, nor the host's gc spans
        rows = [
            r for r in res.metrics.rows
            if r["module"] not in ("(ingress)", "(host)")
        ]
        assert rows
        for r in rows:
            assert 0.0 < r["occupancy"] <= 1.0
            assert 0.0 <= r["dummy_fill"] <= 1.0
            assert r["utilization"] >= 0.0
            assert r["t1"] > r["t0"]
            assert sum(r["closes"].values()) >= 0
        assert sum(r["batches"] for r in rows) == sum(
            s.batches for s in res.module_stats.values()
        )
        table = res.metrics.table()
        assert "occupancy" in table and "utilization" in table
        assert res.metrics.for_module(rows[0]["module"])


# ------------------------------------- member waits and the host spans


def _waits(res, module):
    """The run's summed wait counters of ``module`` over its rows."""
    rows = res.metrics.for_module(module)
    return {
        k: sum(r[k] for r in rows)
        for k in ("collect_s", "queue_s", "service_s", "waited")
    }


WAIT_PATHS = {
    "reference": dict(pipeline=PipelineConfig(reference=True)),
    "fast_path": dict(pipeline=True),
    "general": dict(pipeline=PipelineConfig(fast_path=False)),
}


class TestWaits:
    """collect + queue + service of a module's real members is each member's
    finish − ready, summed, on every path that counts batches."""

    @pytest.mark.parametrize("timeout", [None, "budget"])
    @pytest.mark.parametrize("arrivals", ["poisson", "mmpp"])
    def test_waits_add_up_and_paths_agree(self, timeout, arrivals):
        plan = suite_plan("face", 150.0, 2.5)
        eng = ServingEngine(plan)
        runs = {
            name: eng.run(
                600, 150.0, arrivals=arrivals, seed=1, timeout=timeout,
                observability=True, **kw,
            )
            for name, kw in WAIT_PATHS.items()
        }
        for name, res in runs.items():
            for m, st in res.module_stats.items():
                w = _waits(res, m)
                assert w["waited"] == len(st.latencies) > 0, name
                assert w["collect_s"] >= 0.0 and w["queue_s"] >= -1e-9
                whole = w["collect_s"] + w["queue_s"] + w["service_s"]
                assert whole == pytest.approx(sum(st.latencies), rel=1e-9), (
                    name, m,
                )
            pr = res.pipeline
            if pr is not None:
                # fanout 1 at the source: a member is its frame, so the
                # frame table's finish − avail is the same sum
                src = "face_detect"
                ok = ~np.isnan(pr.finish[src])
                w = _waits(res, src)
                assert w["collect_s"] + w["queue_s"] + w["service_s"] == (
                    pytest.approx(
                        float(np.sum(pr.finish[src][ok] - pr.avail[src][ok])),
                        rel=1e-9,
                    )
                )
        ref = runs["reference"]
        for name in ("fast_path", "general"):
            for m in ref.module_stats:
                a, b = _waits(ref, m), _waits(runs[name], m)
                assert a["waited"] == b["waited"], (name, m)
                for k in ("collect_s", "queue_s", "service_s"):
                    assert b[k] == pytest.approx(a[k], rel=1e-9, abs=1e-12), (
                        name, m, k,
                    )

    def test_deadline_waits_show_in_the_table(self):
        plan = suite_plan("face", 150.0, 2.5)
        res = ServingEngine(plan).run(
            400, 150.0, arrivals="poisson", seed=0, timeout="budget",
            pipeline=True, observability=True,
        )
        table = res.metrics.table()
        assert "collect_ms" in table and "queue_ms" in table
        assert any(
            r["collect_s"] > 0.0 for r in res.metrics.rows if r["waited"]
        )


class TestHostSpans:
    @pytest.mark.parametrize("reference", [True, False])
    def test_gc_hook_is_passive_and_removed(self, reference):
        plan = suite_plan("face", 150.0, 2.5)
        eng = ServingEngine(plan)
        kw = dict(arrivals="mmpp", seed=0, timeout="budget",
                  pipeline=PipelineConfig(reference=reference))
        hooks = list(gc.callbacks)
        threshold = gc.get_threshold()
        gc.set_threshold(50)  # many collections inside the run
        try:
            off = eng.run(800, 150.0, **kw)
            on = eng.run(800, 150.0, observability=True, **kw)
        finally:
            gc.set_threshold(*threshold)
        assert result_key(off) == result_key(on)
        assert gc.callbacks == hooks
        (host,) = on.metrics.for_module("(host)")
        assert host["gc_n"] > 0
        assert 0.0 < host["gc_max_s"] <= host["gc_s"]

    def test_a_run_without_collections_reads_gc_zero(self):
        plan = suite_plan("face", 150.0, 2.5)
        gc.disable()
        try:
            res = ServingEngine(plan).run(200, 150.0, observability=True)
        finally:
            gc.enable()
        (host,) = res.metrics.for_module("(host)")
        assert (host["gc_s"], host["gc_n"]) == (0.0, 0)

    def test_spans_feed_only_the_active_runtime(self):
        from repro.serving.observability import Observability, active, span

        obs = Observability(ObservabilityConfig(trace=False))
        with span("dispatch", "m", 4):
            pass
        with active(obs):
            with span("dispatch", "m", 4), span("sync", "m", 4):
                pass
            with span("dispatch", "m", 1):
                pass
        with span("sync", "m", 1):
            pass
        obs.metrics.flush(1.0, {"m": 1})
        (row,) = obs.metrics.snapshot().for_module("m")
        assert (row["dispatch_n"], row["sync_n"]) == (2, 1)
        assert 0.0 <= row["dispatch_max_s"] <= row["dispatch_s"]


# ----------------------------- relax: scoped inertness (PR-6, promoted PR-8)


class TestExperimentalRelax:
    """The PR-6 finding, re-measured with this layer's forensics.

    PR-6 recorded the relax chain as inert everywhere.  The miss
    forensics show the true scope: on STEADY arrival regimes the
    observed rate never falls below the provisioned target, the tick
    never fires, and runs are bit-identical relax on/off — but on
    diurnal traces stale coarse plans DO deadline-flush near-empty
    padded batches, relaxation retimes those flushes, and the
    ``flush_waste`` miss count drops.  Both halves are pinned here.
    """

    @pytest.mark.parametrize("arrivals", ["uniform", "poisson"])
    def test_steady_regimes_bit_identical(self, arrivals):
        plan = suite_plan("face", 150.0, 2.5)
        eng = ServingEngine(plan)
        n, rate = 1200, 150.0

        def run(relax):
            return eng.run(
                n, rate, arrivals=arrivals, seed=0, timeout="budget",
                frontend=FrontendConfig(dummies=True, burst_deadline=True),
                pipeline=True,
                control=ControlLoopConfig(
                    interval=n / rate / 4, profiles=PROFILES, margin=0.25,
                    relax=relax,
                ),
            )

        assert result_key(run(True)) == result_key(run(False))

    def test_diurnal_relax_fires_and_cuts_flush_waste(self):
        plan = suite_plan("face", 150.0, 2.5)
        eng = ServingEngine(plan)
        n, rate = 1200, 150.0
        period = n / rate
        arr = trace_arrivals(n, rate, seed=0, period=period)

        def run(relax):
            return eng.run(
                n, rate, arrivals=arr, timeout="budget",
                frontend=FrontendConfig(dummies=True, burst_deadline=True),
                pipeline=True,
                control=ControlLoopConfig(
                    interval=period / 4, profiles=PROFILES, margin=0.25,
                    relax=relax,
                ),
            ).miss_report()

        on, off = run(True), run(False)
        assert on.conserved and off.conserved
        assert on.counts.get("flush_waste", 0) < off.counts.get(
            "flush_waste", 0
        )

    def test_knob_validation(self):
        with pytest.raises(ValueError, match="relax_floor"):
            ControlLoopConfig(interval=1.0, relax_floor=0.0)
        with pytest.raises(ValueError, match="relax_every"):
            ControlLoopConfig(interval=1.0, relax_every=0.0)

    def test_deprecated_aliases_removed(self):
        # The experimental_relax* aliases served their one-release
        # deprecation window (promoted in PR 8, dropped in PR 9): passing
        # them must now fail loudly instead of silently mapping.
        for kw in (
            "experimental_relax",
            "experimental_relax_tol",
            "experimental_relax_floor",
            "experimental_relax_every",
        ):
            with pytest.raises(TypeError):
                ControlLoopConfig(interval=1.0, **{kw: 0.2})
        assert not hasattr(ControlLoopConfig(interval=1.0), "experimental_relax")


# ------------------------------------------- BENCH_serving.json merge-write


class TestBenchJson:
    @staticmethod
    def _common():
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
        from benchmarks import common
        return common

    def test_merge_sorted_versioned_deterministic(self, tmp_path):
        common = self._common()
        path = str(tmp_path / "bench.json")
        common.write_bench_json(
            path,
            [{"name": "b_row", "us_per_call": 1.0, "derived": "x"},
             {"name": "a_row", "us_per_call": 2.0, "derived": "y"}],
        )
        doc = json.loads(open(path).read())
        assert doc["schema_version"] == common.SCHEMA_VERSION
        assert [r["name"] for r in doc["benches"]] == ["a_row", "b_row"]
        # partial re-run: update one row, add one — others preserved
        common.write_bench_json(
            path,
            [{"name": "b_row", "us_per_call": 9.0, "derived": "x2"},
             {"name": "c_row", "us_per_call": 3.0, "derived": "z"}],
        )
        doc = json.loads(open(path).read())
        assert [r["name"] for r in doc["benches"]] == [
            "a_row", "b_row", "c_row"
        ]
        assert doc["benches"][1]["us_per_call"] == 9.0
        # idempotent: same rows -> same bytes
        before = open(path).read()
        common.write_bench_json(
            path, [{"name": "c_row", "us_per_call": 3.0, "derived": "z"}]
        )
        assert open(path).read() == before

    def test_corrupt_file_is_replaced_not_fatal(self, tmp_path):
        common = self._common()
        path = str(tmp_path / "bench.json")
        open(path, "w").write("{not json")
        common.write_bench_json(
            path, [{"name": "a", "us_per_call": 1.0, "derived": "d"}]
        )
        doc = json.loads(open(path).read())
        assert [r["name"] for r in doc["benches"]] == ["a"]
