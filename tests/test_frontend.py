"""Serving frontend: dummy streaming, admission control, closed-loop clients.

Covers the ISSUE-2 acceptance criteria: phantom requests fill batches but
never enter statistics, `timeout="budget"` drops its fill-time floor only
when dummies are streamed (with the per-policy floors of the PR-1 path
pinned directly), dummy-padded plans meet their modeled WCL once phantoms
flow, admission control bounds p99 under MMPP overload, closed-loop clients
self-throttle, and frame accounting conserves: completed + shed + dropped
== offered.
"""
import numpy as np
import pytest

from repro.core.dag import AppDAG, Leaf, Workload
from repro.core.dispatch import Policy, expand_machines
from repro.core.harpagon import Plan, PlannerOptions
from repro.core.profiles import Config, ModuleProfile
from repro.core.residual import schedule_module
from repro.serving import ServingEngine
from repro.serving.arrivals import make_arrivals
from repro.serving.frontend import (
    AdmissionController,
    ClosedLoopClients,
    FrontendConfig,
    QueueDepth,
    TokenBucket,
    make_admission,
)
from repro.serving.frontend.dummy import phantom_times


def single_module_plan(
    rate: float,
    slo: float,
    configs,
    *,
    use_dummy: bool = True,
    headroom: float = 0.0,
    policy: Policy = Policy.TC,
) -> Plan:
    profile = ModuleProfile("M", tuple(configs))
    s = schedule_module(
        "M", rate, slo, profile, policy, use_dummy=use_dummy, headroom=headroom
    )
    assert s is not None
    wl = Workload(AppDAG("app", Leaf("M")), {"M": rate}, slo)
    return Plan(wl, PlannerOptions(headroom=headroom), {"M": s}, True, 0.0)


# A dummy-filled residual: 10 req/s cannot fill a b32 batch within L=1.0, so
# Algorithm 1 pads one machine with ~96.7 req/s of dummy traffic (wcl = 2d).
DUMMY_PLAN = single_module_plan(10.0, 1.0, [Config(32, 0.3)])


# ------------------------------------------------------------- budget timeout


class TestBudgetTimeout:
    def test_tc_floor_is_module_fill_rate(self):
        """PR-1 path: under TC every machine's batch fills at the whole
        module rate, so the floor is batch / s.rate."""
        plan = single_module_plan(50.0, 2.0, [Config(8, 0.1)], use_dummy=False)
        eng = ServingEngine(plan, policy=Policy.TC)
        s = plan.schedules["M"]
        machines = expand_machines(list(s.allocs))
        w = eng._module_timeout("M", machines, "budget")
        for mm in machines:
            expect = max(s.budget - mm.config.duration, mm.config.batch / s.rate)
            assert w[mm.mid] == pytest.approx(expect)

    def test_rr_floor_is_machine_share(self):
        """RR/DT machines collect only their own share of the traffic, so a
        fractional machine's floor is longer than a full machine's."""
        plan = single_module_plan(50.0, 2.0, [Config(8, 0.1)], use_dummy=False)
        eng = ServingEngine(plan, policy=Policy.RR)
        s = plan.schedules["M"]
        machines = expand_machines(list(s.allocs))
        w = eng._module_timeout("M", machines, "budget")
        tot = sum(mm.rate for mm in machines)
        for mm in machines:
            fill = mm.config.batch / (s.rate * mm.rate / tot)
            assert w[mm.mid] == pytest.approx(max(s.budget - mm.config.duration, fill))
        # the fractional tail machine has a strictly longer floor
        fracs = [mm for mm in machines if mm.rate < mm.config.throughput - 1e-9]
        fulls = [mm for mm in machines if mm.rate >= mm.config.throughput - 1e-9]
        if fracs and fulls:
            assert w[fracs[0].mid] > w[fulls[0].mid]

    def test_dummy_streaming_drops_the_floor(self):
        """With phantoms streamed, the deadline sits exactly at budget - d."""
        eng = ServingEngine(DUMMY_PLAN)
        s = DUMMY_PLAN.schedules["M"]
        machines = expand_machines(list(s.allocs))
        floored = eng._module_timeout("M", machines, "budget")
        streamed = eng._module_timeout("M", machines, "budget", dummies=True)
        for mm in machines:
            assert floored[mm.mid] == pytest.approx(32 / s.rate)  # fill >> budget
            assert streamed[mm.mid] == pytest.approx(s.budget - mm.config.duration)

    def test_numeric_and_none_pass_through(self):
        eng = ServingEngine(DUMMY_PLAN)
        assert eng._module_timeout("M", [], None) is None
        assert eng._module_timeout("M", [], 0.25) == 0.25
        with pytest.raises(ValueError):
            eng._module_timeout("M", [], "bogus")


# ------------------------------------------------------------ dummy streaming


class TestDummyStreaming:
    def test_phantoms_fill_but_never_enter_stats(self):
        eng = ServingEngine(DUMMY_PLAN)
        res = eng.run(
            600, 10.0, arrivals="poisson", timeout="budget",
            frontend=FrontendConfig(dummies=True),
        )
        st = res.module_stats["M"]
        assert st.phantom > 0
        # every latency entry belongs to a real instance
        assert len(st.latencies) + st.dropped == 600
        assert len(res.e2e_latencies) + res.dropped == 600

    def test_dummy_padded_plan_meets_budget_on_poisson(self):
        """Acceptance: with dummies streamed, a dummy-padded plan under
        timeout="budget" reaches >= the attainment of the floored PR-1 path
        (here: 2d = 0.6 <= slo instead of ~3.5 s fill-floored latencies)."""
        eng = ServingEngine(DUMMY_PLAN)
        floored = eng.run(600, 10.0, arrivals="poisson", timeout="budget")
        streamed = eng.run(
            600, 10.0, arrivals="poisson", timeout="budget",
            frontend=FrontendConfig(dummies=True),
        )
        assert streamed.attainment >= floored.attainment
        assert streamed.attainment >= 0.99
        assert streamed.p99 <= DUMMY_PLAN.workload.slo + 1e-9

    def test_disabled_frontend_is_identity(self):
        """FrontendConfig() must be bit-identical to no frontend at all."""
        plan = single_module_plan(80.0, 1.5, [Config(8, 0.1)])
        eng = ServingEngine(plan)
        for kind in ("uniform", "poisson"):
            a = eng.run(500, 80.0, arrivals=kind)
            b = eng.run(500, 80.0, arrivals=kind, frontend=FrontendConfig())
            np.testing.assert_array_equal(a.e2e_latencies, b.e2e_latencies)
            assert a.shed == b.shed == 0 and a.dropped == b.dropped

    def test_phantom_times_adaptive(self):
        """The injector pads only the deficit: at/above the provisioned rate
        it injects nothing."""
        ready = make_arrivals("uniform", 200, 50.0)
        assert phantom_times(ready, 50.0).size == 0
        assert phantom_times(ready, 40.0).size == 0
        ph = phantom_times(ready, 100.0)
        span = ready[-1] - ready[0]
        assert ph.size == pytest.approx(50.0 * span, abs=1.5)


# ---------------------------------------------------------- admission control


class TestAdmission:
    def test_token_bucket_rate_bound(self):
        """Admitted traffic over the run is bounded by rate * span + burst;
        `admit_live` delegates a token bucket to the time-based `admit`."""
        ctrl = AdmissionController(TokenBucket(rate=50.0, burst=5.0), 50.0)
        live = AdmissionController(TokenBucket(rate=50.0, burst=5.0), 50.0)
        arrivals = make_arrivals("poisson", 2000, 100.0, seed=1)
        admitted = np.array([ctrl.admit(float(t)) for t in arrivals])
        span = arrivals[-1] - arrivals[0]
        assert int(admitted.sum()) <= 50.0 * span + 5.0 + 1
        assert ctrl.admitted == int(admitted.sum())
        assert ctrl.shed == int((~admitted).sum())
        # the backlog argument never moves a token bucket's decision
        np.testing.assert_array_equal(
            [live.admit_live(float(t), 10**6) for t in arrivals], admitted
        )

    def test_queue_depth_bounds_backlog(self):
        """No admitted frame ever waits behind `depth` frames: a scripted
        source backlog, drained FIFO at 10 frames/s, stays within depth."""
        ctrl = AdmissionController(QueueDepth(depth=4), 10.0)
        with pytest.raises(TypeError):
            ctrl.admit(0.0)  # the live backlog is the only queue-depth signal
        arrivals = make_arrivals("mmpp", 500, 20.0, seed=2)
        done: list[float] = []  # service completions of waiting frames
        free = 0.0
        admitted = []
        for t in arrivals:
            done = [d for d in done if d > t]
            ok = ctrl.admit_live(float(t), len(done))
            admitted.append(ok)
            if ok:
                free = max(free, t) + 0.1
                done.append(free)
                # completion at most (depth + 1) service slots after arrival
                assert free - t <= (4 + 1) * 0.1 + 1e-9
            assert len(done) <= 4
        admitted = np.asarray(admitted)
        assert admitted.any() and (~admitted).any()
        assert ctrl.admitted == int(admitted.sum())
        assert ctrl.shed == int((~admitted).sum())

    def test_admission_bounds_p99_under_mmpp_overload(self):
        """Acceptance: at >= provisioned rate under MMPP the uncontrolled
        queues diverge; token-bucket shedding bounds p99."""
        plan = single_module_plan(80.0, 1.0, [Config(8, 0.1)], use_dummy=False)
        eng = ServingEngine(plan)
        kw = dict(arrivals="mmpp", seed=0, timeout="budget", offered_rate=1.3 * 80.0)
        unc = eng.run(3000, 80.0, **kw)
        tb = eng.run(
            3000, 80.0, frontend=FrontendConfig(admission=TokenBucket(burst=4)), **kw
        )
        assert tb.shed > 0
        assert tb.p99 < unc.p99 / 2
        assert tb.p99 < 3.0 * plan.workload.slo  # bounded near the SLO
        assert unc.p99 > 5.0 * plan.workload.slo  # diverged

    def test_per_app_policy_resolution(self):
        spec = {"face": TokenBucket(rate=10.0), "default": "queue_depth"}
        ctrl = make_admission(spec, "face", 50.0)
        assert isinstance(ctrl.policy, TokenBucket) and ctrl._rate == 10.0
        ctrl = make_admission(spec, "traffic", 50.0)
        assert isinstance(ctrl.policy, QueueDepth)
        assert make_admission("none", "face", 50.0) is None
        assert make_admission(None, "face", 50.0) is None
        with pytest.raises(ValueError):
            make_admission("bogus", "face", 50.0)

    def test_shed_frames_count_as_slo_misses(self):
        """Attainment divides by offered frames: an all-shed run attains 0,
        not the seed's vacuous 1.0."""
        plan = single_module_plan(80.0, 1.0, [Config(8, 0.1)], use_dummy=False)
        eng = ServingEngine(plan)
        res = eng.run(
            200, 80.0,
            frontend=FrontendConfig(admission=TokenBucket(rate=1e-6, burst=1.0)),
        )
        assert res.shed >= 199  # bucket admits at most the first frame
        assert res.attainment <= 1 / 200 + 1e-9
        assert res.offered == 200


# --------------------------------------------------------- closed-loop clients


class TestClosedLoop:
    def test_engine_closed_loop_self_throttles(self):
        """Closed-loop offered rate adapts to service latency: with few
        clients the engine serves everything within SLO even though the
        open-loop overload diverges."""
        plan = single_module_plan(80.0, 1.0, [Config(8, 0.1)], use_dummy=False)
        eng = ServingEngine(plan)
        fe = FrontendConfig(clients=ClosedLoopClients(n_clients=8))
        res = eng.run(400, 80.0, frontend=fe)
        assert res.shed == 0
        assert res.offered == 400
        assert res.attempts == 400
        assert res.attainment >= 0.95

    def test_conservation_completed_shed_dropped(self):
        """completed + shed + dropped == offered frames, overload + admission
        + closed loop all at once (full-fanout app)."""
        plan = single_module_plan(80.0, 1.0, [Config(8, 0.1)], use_dummy=False)
        eng = ServingEngine(plan)
        fe = FrontendConfig(
            dummies=True,
            admission=TokenBucket(burst=2.0),
            clients=ClosedLoopClients(n_clients=64, retry_on_shed=True, max_retries=1),
        )
        res = eng.run(500, 80.0, timeout="budget", frontend=fe)
        assert len(res.e2e_latencies) + res.shed + res.dropped == 500
        assert res.offered == 500
        assert res.attempts >= 500


# ----------------------------------------------------------------- headroom


class TestHeadroom:
    def test_cost_scales_inverse_derate(self):
        plan0 = single_module_plan(100.0, 2.0, [Config(8, 0.1)], use_dummy=False)
        plan2 = single_module_plan(
            100.0, 2.0, [Config(8, 0.1)], use_dummy=False, headroom=0.2
        )
        assert plan2.cost == pytest.approx(plan0.cost / 0.8, rel=0.3)
        # machines are derated: assigned rate <= (1 - headroom) * throughput
        for a in plan2.schedules["M"].allocs:
            for mm in expand_machines([a]):
                assert mm.rate <= 0.8 * mm.config.throughput + 1e-9

    def test_tc_wcl_headroom_invariant(self):
        """Theorem 1 collects at the remaining real workload, so the TC WCL
        of a headroom plan never exceeds the zero-slack plan's."""
        s0 = schedule_module(
            "M", 100.0, 2.0, ModuleProfile("M", (Config(8, 0.1),)), Policy.TC,
            use_dummy=False,
        )
        s2 = schedule_module(
            "M", 100.0, 2.0, ModuleProfile("M", (Config(8, 0.1),)), Policy.TC,
            use_dummy=False, headroom=0.2,
        )
        assert s2.wcl <= s0.wcl + 1e-9

    def test_headroom_absorbs_timeout_flushes(self):
        """At 100% utilization any deadline flush permanently degrades
        throughput (ROADMAP open item); with headroom the slack absorbs the
        partial batches and attainment recovers."""
        zero = single_module_plan(80.0, 0.5, [Config(8, 0.1)], use_dummy=False)
        slack = single_module_plan(
            80.0, 0.5, [Config(8, 0.1)], use_dummy=False, headroom=0.2
        )
        r0 = ServingEngine(zero).run(4000, 80.0, arrivals="poisson", timeout=0.25)
        r2 = ServingEngine(slack).run(4000, 80.0, arrivals="poisson", timeout=0.25)
        assert r2.attainment > r0.attainment
        assert r2.attainment >= 0.99
        assert r2.p99 < r0.p99

    def test_invalid_headroom_rejected(self):
        from repro.core.scheduler import generate_config

        with pytest.raises(ValueError):
            generate_config(
                10.0, 1.0, ModuleProfile("M", (Config(8, 0.1),)), headroom=1.0
            )


# ------------------------------------------------------------- ServeResult


class TestServeResult:
    def test_p99_interpolates(self):
        from repro.serving import ServeResult

        lats = [float(i) for i in range(1, 101)]
        r = ServeResult(lats, {}, slo=50.0)
        assert r.p99 == pytest.approx(np.quantile(lats, 0.99))
        # the seed's truncating index understated small-run p99
        assert r.p99 > sorted(lats)[int(0.99 * (len(lats) - 1))] - 1e-9

    def test_attainment_counts_shed_and_dropped(self):
        from repro.serving import ServeResult

        r = ServeResult([0.1, 0.2, 9.9], {}, slo=1.0, shed=5, dropped=2)
        assert r.offered == 10
        assert r.attainment == pytest.approx(2 / 10)
        assert ServeResult([], {}, slo=1.0, shed=7).attainment == 0.0
        assert ServeResult([], {}, slo=1.0).attainment == 1.0
