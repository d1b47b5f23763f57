"""Failure-resilient serving (ISSUE-10): seeded fault injection, watchdog
detection, and graceful-degradation recovery.

Covers: `FaultConfig` validation and the disabled-injector contract (a
disabled config is bit-exact with ``faults=None`` on the pipelined,
control-plane, and tenancy paths), frame conservation
``completed + shed + dropped == offered`` under randomized seeded fault
schedules with every miss classified into exactly one forensics cause,
the suspect→dead watchdog lifecycle (trace instants, counters, the
``failed`` forensic column), out-of-band failure replans with warm-spare
promotion, straggler transients, the bounded-retry ``retry_exhausted``
terminal (dropped, not shed), and the shared pool's ``device_loss``
repack path.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import Planner
from repro.core import baselines as B
from repro.serving import (
    FAULT_KINDS,
    ControlLoopConfig,
    FaultConfig,
    FaultRuntime,
    FrontendConfig,
    ServingEngine,
    SharedPool,
    TokenBucket,
    classify_misses,
)
from repro.serving.arrivals import trace_arrivals
from repro.serving.frontend import ClosedLoopClients
from repro.workloads import synth_profiles
from repro.workloads.apps import app_by_name, make_workload

PROFILES = synth_profiles()


def suite_plan(name, rate, slo):
    plan = Planner(B.HARPAGON).plan(
        make_workload(app_by_name(name), rate, slo), PROFILES
    )
    assert plan.feasible
    return plan


def conserves(res):
    pr = res.pipeline
    return (
        int(pr.completed.sum() + pr.shed.sum() + pr.dropped.sum())
        == res.offered
    )


# ------------------------------------------------------ config validation


class TestFaultConfig:
    def test_disabled_by_default(self):
        cfg = FaultConfig()
        assert not cfg.enabled

    def test_enabled_by_mtbf_or_schedule(self):
        assert FaultConfig(mtbf=5.0).enabled
        assert FaultConfig(schedule=((1.0, "crash"),)).enabled

    @pytest.mark.parametrize(
        "kw",
        [
            dict(mtbf=0.0),
            dict(mtbf=-1.0),
            dict(detect_k=1.0),
            dict(detect_k=0.5),
            dict(straggler_factor=1.0),
            dict(straggler_duration=0.0),
            dict(kinds=("crash", "meteor")),
            dict(schedule=((1.0, "meteor"),)),
            dict(schedule=((-0.5, "crash"),)),
        ],
    )
    def test_rejects_bad_knobs(self, kw):
        with pytest.raises(ValueError):
            FaultConfig(**kw)

    def test_engine_rejects_non_config(self):
        plan = suite_plan("traffic", 100.0, 2.0)
        with pytest.raises(TypeError):
            ServingEngine(plan).run(100, 100.0, faults={"mtbf": 1.0})


# -------------------------------------------- injector/detector unit state


class TestFaultRuntime:
    def test_schedule_drains_before_mtbf_chain(self):
        rt = FaultRuntime(
            FaultConfig(mtbf=10.0, schedule=((2.0, "straggler"), (1.0, "crash")))
        )
        assert rt.next_fault(0.0) == (1.0, "crash")
        assert rt.next_fault(0.0) == (2.0, "straggler")
        t, kind = rt.next_fault(5.0)
        assert t > 5.0 and kind == "crash"

    def test_seeded_determinism(self):
        a = FaultRuntime(FaultConfig(mtbf=3.0, kinds=FAULT_KINDS, seed=7))
        b = FaultRuntime(FaultConfig(mtbf=3.0, kinds=FAULT_KINDS, seed=7))
        seq_a = [a.next_fault(0.0) for _ in range(20)]
        seq_b = [b.next_fault(0.0) for _ in range(20)]
        assert seq_a == seq_b

    def test_escalation_ladder(self):
        rt = FaultRuntime(FaultConfig(schedule=((1.0, "crash"),)))
        assert rt.escalate("M", 0) == "suspect"
        assert rt.escalate("M", 0) == "dead"
        rt.clear("M", 1)  # unrelated machine: no effect
        assert rt.escalate("M", 0) == "dead"
        rt.clear("M", 0)
        assert rt.escalate("M", 0) == "suspect"

    def test_forget_drops_all_state(self):
        rt = FaultRuntime(FaultConfig(schedule=((1.0, "straggler"),)))
        rt.escalate("M", 0)
        rt.slow[("M", 0)] = 4.0
        rt.forget("M", 0)
        assert ("M", 0) not in rt.slow
        assert rt.escalate("M", 0) == "suspect"


# ------------------------------------- disabled injector == faults absent


class TestFaultOffBitExact:
    def _runs(self, plan, n, rate, **kw):
        base = ServingEngine(plan).run(n, rate, **kw)
        off = ServingEngine(plan).run(n, rate, faults=FaultConfig(), **kw)
        return base, off

    def test_flat_path(self):
        plan = suite_plan("traffic", 100.0, 2.0)
        base, off = self._runs(plan, 300, 100.0)
        assert np.array_equal(base.e2e_latencies, off.e2e_latencies)
        assert off.faults is None

    def test_pipelined_path(self):
        plan = suite_plan("face", 150.0, 2.5)
        base, off = self._runs(plan, 400, 150.0, pipeline=True)
        assert np.array_equal(
            base.pipeline.e2e, off.pipeline.e2e, equal_nan=True
        )
        assert off.faults is None

    def test_control_path(self):
        plan = suite_plan("pose", 60.0, 3.0)
        arr = trace_arrivals(400, 60.0, seed=0, period=400 / 60.0)
        kw = dict(
            arrivals=arr, pipeline=True, timeout="budget",
            frontend=FrontendConfig(dummies=True, burst_deadline=True),
        )
        base = ServingEngine(plan).run(
            400, 60.0,
            control=ControlLoopConfig(interval=400 / 60.0 / 8, profiles=PROFILES),
            **kw,
        )
        off = ServingEngine(plan).run(
            400, 60.0,
            control=ControlLoopConfig(interval=400 / 60.0 / 8, profiles=PROFILES),
            faults=FaultConfig(),
            **kw,
        )
        assert np.array_equal(
            base.pipeline.e2e, off.pipeline.e2e, equal_nan=True
        )

    def test_tenancy_path(self):
        plans = {
            a: suite_plan(a, r, s)
            for a, r, s in (("traffic", 100.0, 2.0), ("pose", 60.0, 3.0))
        }
        base = SharedPool(plans).run(300)
        off = SharedPool(plans).run(300, faults=FaultConfig())
        for a in plans:
            assert np.array_equal(
                base.results[a].pipeline.e2e,
                off.results[a].pipeline.e2e,
                equal_nan=True,
            )


# ----------------------------- conservation under randomized fault storms


class TestConservationUnderFaults:
    """The property test: ``completed + shed + dropped == offered`` exactly,
    and every miss classifies into exactly one forensics cause, under any
    fault schedule (seeded randomized storms; hypothesis-free by design —
    no new dependency)."""

    APPS = (("traffic", 100.0, 2.0), ("face", 150.0, 2.5), ("pose", 60.0, 3.0))

    def _storm(self, rng, horizon):
        n = int(rng.integers(1, 4))
        kinds = ("crash", "straggler")
        return tuple(
            sorted(
                (float(rng.uniform(0.1, horizon)), kinds[int(rng.integers(2))])
                for _ in range(n)
            )
        )

    def test_randomized_schedules_no_control(self):
        rng = np.random.default_rng(0)
        for trial in range(6):
            name, rate, slo = self.APPS[trial % len(self.APPS)]
            plan = suite_plan(name, rate, slo)
            n = 400
            sched = self._storm(rng, n / rate * 0.8)
            res = ServingEngine(plan).run(
                n, rate, pipeline=True,
                faults=FaultConfig(
                    schedule=sched, seed=int(rng.integers(1000)), detect_k=2.0
                ),
            )
            assert conserves(res), (name, sched)
            rep = classify_misses(res.pipeline, slo)
            assert rep.conserved, (name, sched)

    def test_randomized_schedules_with_control(self):
        rng = np.random.default_rng(1)
        for trial in range(3):
            name, rate, slo = self.APPS[trial % len(self.APPS)]
            plan = suite_plan(name, rate, slo / 1.25)
            n = 480
            period = n / rate
            arr = trace_arrivals(n, rate, seed=0, period=period)
            sched = self._storm(rng, period * 0.8)
            res = ServingEngine(plan).run(
                n, rate, arrivals=arr, pipeline=True, timeout="budget",
                frontend=FrontendConfig(dummies=True, burst_deadline=True),
                control=ControlLoopConfig(
                    interval=period / 8, profiles=PROFILES, margin=0.35
                ),
                faults=FaultConfig(
                    schedule=sched, seed=int(rng.integers(1000)), detect_k=2.0
                ),
            )
            assert conserves(res), (name, sched)
            rep = classify_misses(res.pipeline, slo, res.epochs)
            assert rep.conserved, (name, sched)


# ------------------------------------------- detection lifecycle + trace


class TestDetectionAndRecovery:
    def _crash_run(self, observability=False, control=None, detect_k=2.0):
        plan = suite_plan("face", 150.0, 2.5)
        return ServingEngine(plan).run(
            600, 150.0, pipeline=True, control=control,
            observability=observability,
            faults=FaultConfig(schedule=((1.0, "crash"),), detect_k=detect_k),
        )

    def test_crash_is_detected_and_requeued(self):
        res = self._crash_run()
        assert res.faults == {
            "injected": 1,
            "killed": res.faults["killed"],
            "requeued": res.faults["requeued"],
        }
        assert res.faults["killed"] == 1
        assert res.faults["requeued"] > 0
        assert conserves(res)
        failed = res.pipeline.failed
        assert failed is not None and failed.sum() > 0

    def test_failure_forensics_causes(self):
        res = self._crash_run()
        rep = classify_misses(res.pipeline, 2.5)
        assert rep.conserved
        touched = (
            rep.counts.get("machine_failure", 0)
            + rep.counts.get("recovery_transient", 0)
        )
        assert touched > 0  # failure attribution trumps epoch attribution

    def test_trace_instants(self):
        res = self._crash_run(observability=True)
        names = {ev[4] for ev in res.trace.events()}
        assert {"suspect", "fail", "requeue"} <= names

    def test_failure_replan_fires_out_of_band(self):
        plan = suite_plan("face", 150.0, 2.5 / 1.25)
        n, rate = 600, 150.0
        period = n / rate
        res = ServingEngine(plan).run(
            n, rate,
            arrivals=trace_arrivals(n, rate, seed=0, period=period),
            pipeline=True, timeout="budget",
            frontend=FrontendConfig(dummies=True, burst_deadline=True),
            control=ControlLoopConfig(
                interval=period / 4, profiles=PROFILES, margin=0.35
            ),
            faults=FaultConfig(schedule=((period / 2.2, "crash"),), detect_k=2.0),
        )
        assert conserves(res)
        if res.faults["killed"] and any(
            "failure_replan" in a
            for e in res.epochs
            for a in e.actions.values()
        ):
            return  # the out-of-band replan landed and was recorded
        # the epoch swap may legitimately beat the watchdog verdict: then
        # the stranded members are still rescued without a replan
        assert res.faults["requeued"] > 0 or res.faults["killed"] == 0

    def test_straggler_recovers_without_kill(self):
        plan = suite_plan("traffic", 100.0, 2.0)
        res = ServingEngine(plan).run(
            500, 100.0, pipeline=True,
            faults=FaultConfig(
                schedule=((1.0, "straggler"),),
                straggler_factor=1.5,
                straggler_duration=0.2,
                detect_k=4.0,
            ),
        )
        # a mild, short slowdown must not be declared dead
        assert res.faults["injected"] == 1
        assert res.faults["killed"] == 0
        assert conserves(res)

    def test_severe_straggler_is_killed_as_dead(self):
        plan = suite_plan("traffic", 100.0, 2.0)
        res = ServingEngine(plan).run(
            500, 100.0, pipeline=True,
            faults=FaultConfig(
                schedule=((1.0, "straggler"),),
                straggler_factor=50.0,
                straggler_duration=4.0,
                detect_k=2.0,
            ),
        )
        # slow-vs-dead is indistinguishable to the watchdog: a straggler
        # that misses two windows is correctly killed, frames conserved
        # (the requeue wave may push a sibling past its own window too)
        assert res.faults["killed"] >= 1
        assert conserves(res)

    def test_mtbf_chain_is_reproducible(self):
        plan = suite_plan("pose", 60.0, 3.0)
        kw = dict(
            pipeline=True,
            faults=FaultConfig(mtbf=2.0, seed=11, detect_k=2.0),
        )
        a = ServingEngine(plan).run(400, 60.0, **kw)
        b = ServingEngine(plan).run(400, 60.0, **kw)
        assert np.array_equal(a.pipeline.e2e, b.pipeline.e2e, equal_nan=True)
        assert a.faults == b.faults


# --------------------------------------------- bounded retries (ISSUE-10.1)


class TestRetryExhausted:
    def _overloaded(self, max_retries):
        plan = suite_plan("traffic", 100.0, 2.0)
        fe = FrontendConfig(
            admission=TokenBucket(rate=40.0, burst=2.0),
            clients=ClosedLoopClients(
                n_clients=64, retry_on_shed=True,
                max_retries=max_retries, backoff=0.01,
            ),
        )
        return ServingEngine(plan).run(400, 80.0, frontend=fe, pipeline=True)

    def test_exhausted_frames_are_dropped_not_shed(self):
        res = self._overloaded(max_retries=2)
        pr = res.pipeline
        # the half-rate bucket forces terminal denials; every exhausted
        # frame is *dropped* (admitted demand the system failed after
        # re-offers), never folded into first-sight shed
        assert res.dropped > 0
        assert conserves(res)
        assert res.attempts >= 400
        # dropped-at-ingress frames never entered the pipeline
        assert not np.any(pr.dropped & pr.completed)

    def test_retry_cause_lands_in_trace(self):
        plan = suite_plan("traffic", 100.0, 2.0)
        fe = FrontendConfig(
            admission=TokenBucket(rate=40.0, burst=2.0),
            clients=ClosedLoopClients(
                n_clients=64, retry_on_shed=True, max_retries=1, backoff=0.01
            ),
        )
        res = ServingEngine(plan).run(
            400, 80.0, frontend=fe, pipeline=True, observability=True
        )
        names = [ev[4] for ev in res.trace.events()]
        assert any("retry_exhausted" in n for n in names)

    def test_zero_retries_terminal_at_first_denial(self):
        plan = suite_plan("traffic", 100.0, 2.0)
        fe = FrontendConfig(
            admission=TokenBucket(rate=40.0, burst=2.0),
            clients=ClosedLoopClients(
                n_clients=64, retry_on_shed=True, max_retries=0
            ),
        )
        res = ServingEngine(plan).run(400, 80.0, frontend=fe, pipeline=True)
        # no re-offer ever happened, so denials are first-sight sheds
        assert res.dropped == 0
        assert res.shed > 0
        assert conserves(res)


# -------------------------------------------------- shared-pool device loss


class TestDeviceLoss:
    def test_pool_crash_conserves_every_app(self):
        plans = {
            a: suite_plan(a, r, s)
            for a, r, s in (("traffic", 100.0, 2.0), ("pose", 60.0, 3.0))
        }
        pool = SharedPool(plans)
        res = pool.run(
            300,
            faults=FaultConfig(
                schedule=((0.8, "device_loss"),), seed=5, detect_k=2.0
            ),
        )
        for a, r in res.results.items():
            pr = r.pipeline
            assert (
                int(pr.completed.sum() + pr.shed.sum() + pr.dropped.sum())
                == r.offered
            ), a
