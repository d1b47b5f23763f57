"""ServiceTimeSource backends and the control plane's profile correction
(ISSUE-6): the simulator-to-serving bridge.

Covers: the analytic backend's bit-exactness (source unset vs an explicit
`AnalyticServiceTime` — flat and pipelined), trace-backend determinism under
a fixed seed (and divergence from analytic once samples differ), the trace
key ladder ((module, batch, hardware) before (module, batch) before module),
live-backend measurement/caching/`to_trace` freezing, `resolve_service_time`
spec normalization, and `ControlRuntime` correction convergence — a
1.3x-miscalibrated profile's model-vs-measured `duration_err` collapses
within two epochs once replans run against the corrected profiles.
"""
import numpy as np
import pytest

from repro.core import Planner
from repro.core.dispatch import Config, Machine
from repro.serving import (
    AnalyticServiceTime,
    ControlLoopConfig,
    FrontendConfig,
    LiveServiceTime,
    ServingEngine,
    TraceServiceTime,
    resolve_service_time,
)
from repro.workloads import synth_profiles
from repro.workloads.apps import app_by_name, make_workload

PROFILES = synth_profiles()


def _face_plan(rate=150.0, slo=2.5):
    wl = make_workload(app_by_name("face"), rate, slo)
    plan = Planner().plan(wl, PROFILES)
    assert plan.feasible
    return plan


def _machine(module="m", batch=8, duration=0.05, hardware="tpu-v4"):
    cfg = Config(batch=batch, duration=duration, hardware=hardware)
    return Machine(mid=0, config=cfg, rate=1.0)


class TestAnalyticBitExact:
    """service_time=None and an explicit analytic source are the same run."""

    @pytest.mark.parametrize("pipeline", [False, True])
    def test_bit_exact(self, pipeline):
        plan = _face_plan()
        eng = ServingEngine(plan)
        kw = dict(arrivals="poisson", seed=3, pipeline=pipeline)
        base = eng.run(2000, 150.0, **kw)
        explicit = eng.run(2000, 150.0, service_time=AnalyticServiceTime(), **kw)
        assert np.array_equal(
            base.e2e_latencies, explicit.e2e_latencies, equal_nan=True
        )

    def test_analytic_string_resolves_to_none(self):
        assert resolve_service_time(None) is None
        assert resolve_service_time("analytic") is None


class TestTraceBackend:
    def test_deterministic_under_seed(self):
        plan = _face_plan()
        eng = ServingEngine(plan)
        samples = {
            m: [c.duration * f for c in PROFILES[m].configs for f in (0.9, 1.2)]
            for m in plan.schedules
        }
        mk = lambda: TraceServiceTime(samples, jitter=0.1, seed=7)
        a = eng.run(1500, 150.0, arrivals="poisson", pipeline=True,
                    service_time=mk())
        b = eng.run(1500, 150.0, arrivals="poisson", pipeline=True,
                    service_time=mk())
        assert np.array_equal(a.e2e_latencies, b.e2e_latencies, equal_nan=True)

    def test_differs_from_analytic(self):
        plan = _face_plan()
        eng = ServingEngine(plan)
        src = TraceServiceTime(
            {m: [c.duration * 1.5 for c in PROFILES[m].configs]
             for m in plan.schedules}
        )
        base = eng.run(1500, 150.0, arrivals="poisson", pipeline=True)
        traced = eng.run(1500, 150.0, arrivals="poisson", pipeline=True,
                         service_time=src)
        assert not np.array_equal(
            base.e2e_latencies, traced.e2e_latencies, equal_nan=True
        )

    def test_key_ladder(self):
        m4 = _machine(batch=8, duration=0.05, hardware="tpu-v4")
        m5 = _machine(batch=8, duration=0.05, hardware="tpu-v5p")
        src = TraceServiceTime({
            ("m", 8, "tpu-v4"): [0.11],
            ("m", 8): [0.22],
            "m": [0.33],
        })
        assert src.duration("m", m4, 8) == pytest.approx(0.11)
        assert src.duration("m", m5, 8) == pytest.approx(0.22)
        m_other = _machine(batch=4, duration=0.05)
        assert src.duration("m", m_other, 4) == pytest.approx(0.33)
        # no samples at all: profiled fallback
        assert src.duration("other", m4, 8) == pytest.approx(0.05)

    def test_sequence_axis_and_reset(self):
        src = TraceServiceTime({("m", 8): [0.1, 0.2, 0.3]})
        m = _machine(batch=8)
        draws = [src.duration("m", m, 8) for _ in range(4)]
        assert draws == pytest.approx([0.1, 0.2, 0.3, 0.1])  # k mod len
        src.reset()
        assert src.duration("m", m, 8) == pytest.approx(0.1)

    def test_validation(self):
        with pytest.raises(ValueError):
            TraceServiceTime({("m", 8): [0.1, -0.2]})
        with pytest.raises(ValueError):
            TraceServiceTime({}, jitter=-1.0)


class TestLiveBackend:
    def test_measures_and_caches(self):
        calls = []
        src = LiveServiceTime({"m": lambda b: calls.append(b)}, warmup=1)
        m = _machine(batch=8)
        for _ in range(4):
            d = src.duration("m", m, 8)
            assert d > 0.0
        # warmup + 1 timed calls, then the cached steady mean is served
        assert calls == [8, 8]
        assert ("m", 8) in src.measured

    def test_no_executor_falls_back_to_profile(self):
        """A module with no executor is an error: no silent profiled time."""
        src = LiveServiceTime({"other": lambda b: None})
        with pytest.raises(KeyError, match="no executor"):
            src.duration("m", _machine(duration=0.07), 8)

    def test_to_trace_freezes_post_warmup(self):
        src = LiveServiceTime({"m": lambda b: None}, warmup=1, cache=False)
        m = _machine(batch=8)
        for _ in range(3):
            src.duration("m", m, 8)
        trace = src.to_trace()
        assert trace.samples[("m", 8)] == src.measured[("m", 8)][1:]

    def test_resolve_live_requires_executors(self):
        with pytest.raises(ValueError):
            resolve_service_time("live")
        src = resolve_service_time("live", {"m": lambda b: None})
        assert isinstance(src, LiveServiceTime)

    def test_resolve_rejects_bad_specs(self):
        with pytest.raises(ValueError):
            resolve_service_time("trace")
        with pytest.raises(TypeError):
            resolve_service_time(123)

    @pytest.mark.slow
    def test_live_engine_smoke(self):
        plan = _face_plan()
        eng = ServingEngine(
            plan, executors={m: (lambda b: None) for m in plan.schedules}
        )
        res = eng.run(300, 150.0, arrivals="poisson", pipeline=True,
                      service_time="live")
        lat = np.asarray(res.e2e_latencies)
        assert np.isfinite(lat[~np.isnan(lat)]).all()


class TestCorrectionConvergence:
    def test_converges_within_two_epochs(self):
        """A 1.3x-miscalibrated profile: epoch 1 audits duration_err ~0.3,
        the replan adopts the corrected profiles, and the error collapses
        (the active plan's modeled durations now match the trace)."""
        rate, slo = 150.0, 2.5
        plan = _face_plan(rate, slo)
        samples = {
            (m, c.batch, c.hardware): [c.duration * 1.3]
            for m, p in PROFILES.items()
            for c in p.configs
        }
        src = TraceServiceTime(samples)
        ctrl = ControlLoopConfig(interval=4.0, profiles=PROFILES, margin=0.2)
        eng = ServingEngine(plan)
        res = eng.run(
            4000, rate, arrivals="poisson", pipeline=True,
            frontend=FrontendConfig(dummies=True, burst_deadline=True),
            timeout="budget", control=ctrl, service_time=src,
        )
        errs = [e.duration_err for e in res.epochs]
        assert len(errs) >= 4
        # epoch 1 closes on the uncorrected plan: full 30% model error
        assert errs[1] == pytest.approx(0.3, abs=0.05)
        # within two epochs the replan runs on corrected profiles
        assert all(e <= 0.05 for e in errs[3:] if e > 0.0)
        corrected = [e.corrections for e in res.epochs if e.corrections]
        assert corrected, "no profile correction was recorded"
        for m, s in corrected[-1].items():
            assert s == pytest.approx(1.3, rel=0.05)

    def test_corrections_off(self):
        """correct_profiles=False still audits the error but never repairs."""
        rate = 150.0
        plan = _face_plan(rate)
        src = TraceServiceTime({
            (m, c.batch, c.hardware): [c.duration * 1.3]
            for m, p in PROFILES.items()
            for c in p.configs
        })
        ctrl = ControlLoopConfig(
            interval=4.0, profiles=PROFILES, margin=0.2,
            correct_profiles=False,
        )
        res = ServingEngine(plan).run(
            3000, rate, arrivals="poisson", pipeline=True,
            frontend=FrontendConfig(dummies=True, burst_deadline=True),
            timeout="budget", control=ctrl, service_time=src,
        )
        errs = [e.duration_err for e in res.epochs if e.duration_err > 0.0]
        assert errs and all(e == pytest.approx(0.3, abs=0.06) for e in errs)
        assert not any(e.corrections for e in res.epochs)
