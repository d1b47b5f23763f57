"""Pipelined serving core: per-frame DAG co-simulation (ISSUE-3 acceptance).

Covers: golden equivalence of the default path (the vectorized kernel, via
the segment fast path) against the event-by-event oracle loop on
multi-stage DAGs (the kernel-vs-event-core cross-validation extended
through the DAG), the uniform-arrivals mean-vs-analytic-WCL-sum acceptance
bound, the splitter-budget property (feasible `split_lc` budgets hold
end-to-end; budget-overrun attribution sums exactly to the end-to-end
overrun), backpressure under bounded queues, correlated per-frame
stochastic fanout, event-interleaved closed-loop clients obeying Little's
law, and the per-rank `timeout="budget"` fill-time floor.
"""
import numpy as np
import pytest

from repro.core import Planner
from repro.core import baselines as B
from repro.core.dag import AppDAG, Leaf, Workload, par, series, sp_critical_masks
from repro.core.dispatch import Policy, expand_machines, remaining_workloads
from repro.core.harpagon import Plan, PlannerOptions
from repro.core.profiles import Config, ModuleProfile
from repro.core.residual import schedule_module
from repro.serving import ServingEngine
from repro.serving.frontend import ClosedLoopClients, FrontendConfig, TokenBucket
from repro.serving.pipeline import (
    AccumulatorFanout,
    FanoutSpec,
    PipelineConfig,
    draw_counts,
)
from repro.serving.replay import expand_fanout
from repro.workloads import synth_profiles, synth_workloads
from repro.workloads.apps import ACTDET, CAPTION, FACE, FANOUT, TRAFFIC, make_workload

PROFILES = synth_profiles()
REF = PipelineConfig(reference=True)


def chain_plan(specs, rate: float, slo: float, fanouts=None) -> Plan:
    """Build a series-chain plan from ``(name, configs, budget)`` specs."""
    leaves = [Leaf(n) for n, _, _ in specs]
    app = AppDAG("chain", series(*leaves))
    fanouts = fanouts or {}
    scheds, rates = {}, {}
    for name, cfgs, budget in specs:
        r = rate * fanouts.get(name, 1.0)
        s = schedule_module(
            name, r, budget, ModuleProfile(name, tuple(cfgs)), Policy.TC,
            use_dummy=False,
        )
        assert s is not None, name
        scheds[name] = s
        rates[name] = r
    return Plan(Workload(app, rates, slo), PlannerOptions(), scheds, True, 0.0)


def suite_plan(app, rate, slo):
    plan = Planner(B.HARPAGON).plan(make_workload(app, rate=rate, slo=slo), PROFILES)
    assert plan.feasible
    return plan


# ------------------------------------------------- golden: pipeline == kernel


class TestGoldenEquivalence:
    """With unbounded queues and deterministic fanout the default run
    delegates to the vectorized kernel, which must reproduce the
    event-by-event oracle loop (``PipelineConfig(reference=True)``): same
    instance streams, same batch boundaries, same per-frame e2e — the
    kernel-vs-event-core cross-validation extended through multi-stage
    DAGs."""

    @pytest.mark.parametrize("kind", ["uniform", "poisson", "mmpp"])
    def test_two_stage_dag_matches_kernel(self, kind):
        plan = suite_plan(FACE, 150.0, 2.5)
        eng = ServingEngine(plan)
        flat = eng.run(600, 150.0, arrivals=kind, seed=5, pipeline=REF)
        pipe = eng.run(600, 150.0, arrivals=kind, seed=5)
        np.testing.assert_allclose(
            np.asarray(pipe.e2e_latencies), np.asarray(flat.e2e_latencies), atol=1e-9
        )
        for m in plan.workload.app.modules:
            assert pipe.module_stats[m].batches == flat.module_stats[m].batches
            np.testing.assert_allclose(
                np.sort(pipe.module_stats[m].latencies),
                np.sort(flat.module_stats[m].latencies),
                atol=1e-9,
            )

    @pytest.mark.parametrize(
        "app,rate,slo",
        [(TRAFFIC, 100.0, 2.0), (CAPTION, 90.0, 2.5), (ACTDET, 80.0, 3.0)],
    )
    def test_wider_dags_match_kernel(self, app, rate, slo):
        """Parallel branches (traffic/actdet) and fanout < 1 (caption)."""
        plan = suite_plan(app, rate, slo)
        eng = ServingEngine(plan)
        flat = eng.run(500, rate, arrivals="mmpp", seed=2, pipeline=REF)
        pipe = eng.run(500, rate, arrivals="mmpp", seed=2)
        assert len(pipe.e2e_latencies) == len(flat.e2e_latencies)
        np.testing.assert_allclose(
            np.asarray(pipe.e2e_latencies), np.asarray(flat.e2e_latencies), atol=1e-9
        )
        assert (pipe.shed, pipe.dropped) == (flat.shed, flat.dropped)

    def test_budget_timeout_matches_kernel(self):
        plan = suite_plan(FACE, 150.0, 2.5)
        eng = ServingEngine(plan)
        flat = eng.run(
            500, 150.0, arrivals="poisson", seed=1, timeout="budget", pipeline=REF
        )
        pipe = eng.run(500, 150.0, arrivals="poisson", seed=1, timeout="budget")
        np.testing.assert_allclose(
            np.asarray(pipe.e2e_latencies), np.asarray(flat.e2e_latencies), atol=1e-9
        )


# ------------------------------------------------- acceptance: mean vs WCL sum


class TestAnalyticWCL:
    def test_uniform_mean_within_5pct_of_wcl_sum(self):
        """Acceptance: on uniform arrivals the pipelined mean e2e matches
        the analytic critical-path WCL sum within 5% (service-dominated
        two-stage chain: collection terms are the only modeled slack)."""
        plan = chain_plan(
            [("A", [Config(8, 1.0)], 1.1), ("B", [Config(8, 1.0)], 1.1)],
            400.0, 2.2,
        )
        res = ServingEngine(plan).run(1200, 400.0, pipeline=True)
        wcl_sum = plan.e2e_latency
        mean = float(np.mean(res.e2e_latencies))
        assert abs(mean - wcl_sum) / wcl_sum <= 0.05
        # the WCL sum is an upper envelope on uniform arrivals
        assert res.p99 <= wcl_sum + 1e-9

    def test_suite_mean_tracks_wcl_sum(self):
        """Seed apps stay within the batch-collection slack of the WCL sum
        (mean below, p99 near): the pipelined numbers are the analytic
        model's trajectory, not a new regime."""
        for app, rate, slo in ((FACE, 150.0, 2.5), (TRAFFIC, 100.0, 2.0)):
            plan = suite_plan(app, rate, slo)
            res = ServingEngine(plan).run(800, rate, pipeline=True)
            wcl_sum = plan.e2e_latency
            mean = float(np.mean(res.e2e_latencies))
            assert mean <= wcl_sum + 1e-9, app.name
            assert mean >= 0.5 * wcl_sum, app.name


# ------------------------------------------------- splitter-budget property


class TestSplitterBudgets:
    def test_feasible_lc_budgets_hold_end_to_end(self):
        """Property: when `split_lc` (via the planner) returns a feasible
        budget over integer-exact covers, every frame's pipelined e2e is
        <= SLO on uniform arrivals."""
        rng = np.random.default_rng(11)
        checked = 0
        for _ in range(6):
            b1, b2 = int(rng.choice([4, 8, 16])), int(rng.choice([2, 4, 8]))
            t1, t2 = int(rng.choice([10, 20, 40])), int(rng.choice([10, 20, 40]))
            d1, d2 = b1 / t1, b2 / t2
            # rate = integer multiple of both throughputs: no fractional tail
            rate = float(int(rng.integers(2, 5)) * np.lcm(t1, t2))
            wcl1, wcl2 = d1 + b1 / rate, d2 + b2 / rate
            slo = (wcl1 + wcl2) * 1.05
            plan = chain_plan(
                [("A", [Config(b1, d1)], wcl1 * 1.01), ("B", [Config(b2, d2)], wcl2 * 1.01)],
                rate, slo,
            )
            res = ServingEngine(plan).run(600, rate, pipeline=True)
            e2e = np.asarray(res.e2e_latencies)
            assert e2e.size and e2e.max() <= slo + 1e-9, (b1, b2, d1, d2, rate)
            checked += 1
        assert checked == 6

    def test_suite_attainment_dummy_free(self):
        """Across suite workloads whose plans carry no dummy padding, the
        default planner's pipelined attainment on uniform arrivals stays
        >= 0.99 — machines downstream of batched stages see bursty
        collection the steady-state Theorem-1 WCL does not model (the PR-3
        finding, closed by ISSUE-4) — while the burst-aware planner
        (``PlannerOptions(burst_aware=True)``, checking every machine at
        ``d + b/w + b_up/rate_up``) no longer overshoots at all."""
        import dataclasses

        opts_ba = dataclasses.replace(B.HARPAGON, name="harp-burst", burst_aware=True)
        checked = checked_ba = 0
        for wl in synth_workloads(40):
            plan = Planner(B.HARPAGON).plan(wl, PROFILES)
            if not plan.feasible:
                continue
            if any(a.dummy > 0 for s in plan.schedules.values() for a in s.allocs):
                continue
            fr = wl.rates[wl.app.modules[0]] / FANOUT[wl.app.name][wl.app.modules[0]]
            res = ServingEngine(plan).run(300, fr, pipeline=True)
            assert res.attainment >= 0.99, wl.tag
            checked += 1
            ba = Planner(opts_ba).plan(wl, PROFILES)
            if not ba.feasible or any(
                a.dummy > 0 for s in ba.schedules.values() for a in s.allocs
            ):
                continue
            res_ba = ServingEngine(ba).run(300, fr, pipeline=True)
            assert res_ba.attainment == 1.0, wl.tag
            checked_ba += 1
        assert checked >= 10 and checked_ba >= 10

    def test_burst_aware_closes_known_overshoots(self):
        """The two suite points where the default plan's realized collection
        exceeds a tight SLO by a few percent (one on a fractional tail, one
        on a full short-fill machine): the burst-aware correction makes both
        attain 1.0 at a bounded cost premium."""
        import dataclasses

        from repro.workloads.apps import app_by_name

        opts_ba = dataclasses.replace(B.HARPAGON, name="harp-burst", burst_aware=True)
        for name, rate, slo in (("traffic", 242.59, 1.5), ("face", 20.5, 1.5)):
            wl = make_workload(app_by_name(name), rate, slo)
            base = Planner(B.HARPAGON).plan(wl, PROFILES)
            assert base.feasible
            res = ServingEngine(base).run(300, rate, pipeline=True)
            assert res.attainment < 1.0  # the finding, reproduced
            ba = Planner(opts_ba).plan(wl, PROFILES)
            assert ba.feasible
            assert not any(
                a.dummy > 0 for s in ba.schedules.values() for a in s.allocs
            )
            res_ba = ServingEngine(ba).run(300, rate, pipeline=True)
            assert res_ba.attainment == 1.0, name
            assert ba.cost <= base.cost * 1.5  # bounded robustness premium

    @pytest.mark.parametrize("kind", ["uniform", "mmpp"])
    def test_attribution_sums_to_e2e_overrun(self, kind):
        """Acceptance: per-module budget-overrun attribution sums exactly to
        the frame's end-to-end overrun beyond its critical-path budget sum
        — for every completed frame, also under bursty overload."""
        plan = suite_plan(ACTDET, 80.0, 3.0)
        eng = ServingEngine(plan)
        res = eng.run(
            500, 80.0, arrivals=kind, seed=7, pipeline=True,
            offered_rate=80.0 * (1.2 if kind == "mmpp" else 1.0),
        )
        pr = res.pipeline
        budgets = {m: s.budget for m, s in plan.schedules.items()}
        attr, path_budget = pr.overrun_attribution(budgets)
        total = sum(attr[m] for m in pr.modules)
        done = pr.completed
        assert done.any()
        np.testing.assert_allclose(
            total[done], pr.e2e[done] - path_budget[done], atol=1e-9
        )
        # the decomposition rides on the realized critical path
        lat, masks = pr.critical_path()
        np.testing.assert_allclose(lat[done], pr.e2e[done], atol=1e-9)
        for f in np.flatnonzero(done)[:50]:
            on = [m for m in pr.modules if masks[m][f]]
            assert on, f

    def test_overrun_by_module_flags_the_blown_budget(self):
        """A two-stage chain whose splitter handed B an unachievable budget:
        late frames' overrun must be attributed to B, not A."""
        import dataclasses

        plan = chain_plan(
            [("A", [Config(4, 0.1)], 0.3), ("B", [Config(16, 0.4)], 0.9)],
            40.0, 0.7,
        )
        s_b = dataclasses.replace(plan.schedules["B"], budget=0.45)
        plan = dataclasses.replace(
            plan, schedules={**plan.schedules, "B": s_b}
        )
        res = ServingEngine(plan).run(400, 40.0, pipeline=True)
        pr = res.pipeline
        budgets = {m: s.budget for m, s in plan.schedules.items()}
        assert (pr.e2e > plan.workload.slo).any()
        over = pr.overrun_by_module(budgets, plan.workload.slo)
        assert over["B"] > 0.0
        assert over["B"] > over["A"]


# ------------------------------------------------- backpressure


class TestBackpressure:
    def _two_stage(self):
        # A is fast and cheap; B is slow: bounded ingress at B must stall A
        return chain_plan(
            [("A", [Config(4, 0.05)], 0.2), ("B", [Config(8, 0.8)], 1.0)],
            40.0, 1.4,
        )

    def test_bounded_queue_stalls_upstream(self):
        plan = self._two_stage()
        eng = ServingEngine(plan)
        free = eng.run(400, 40.0, arrivals="mmpp", seed=3, pipeline=True)
        tight = eng.run(
            400, 40.0, arrivals="mmpp", seed=3,
            pipeline=PipelineConfig(queue_cap=8),
        )
        # backpressure pushes waiting upstream: B's measured in-stage
        # instance latency strictly shrinks (its backlog is bounded) while
        # the frame pays the wait at the blocked hand-off instead — e2e
        # never improves and no frame is lost
        b_free = np.asarray(free.module_stats["B"].latencies)
        b_tight = np.asarray(tight.module_stats["B"].latencies)
        assert b_tight.max() < b_free.max() - 1e-9
        assert np.mean(tight.e2e_latencies) >= np.mean(free.e2e_latencies) - 1e-9
        # conservation: every offered frame accounted
        assert len(tight.e2e_latencies) + tight.shed + tight.dropped == 400

    def test_unbounded_cap_is_identity(self):
        plan = self._two_stage()
        eng = ServingEngine(plan)
        a = eng.run(300, 40.0, arrivals="poisson", seed=1, pipeline=True)
        b = eng.run(
            300, 40.0, arrivals="poisson", seed=1,
            pipeline=PipelineConfig(queue_cap=None),
        )
        np.testing.assert_array_equal(a.e2e_latencies, b.e2e_latencies)

    def test_queue_cap_floors_at_largest_batch(self):
        """A cap below the largest batch size could never form a batch; the
        stage floors it so formation always completes."""
        plan = self._two_stage()
        res = ServingEngine(plan).run(
            300, 40.0, pipeline=PipelineConfig(queue_cap=1)
        )
        assert len(res.e2e_latencies) == 300
        assert res.dropped == 0

    def test_queue_cap_validation(self):
        with pytest.raises(ValueError):
            PipelineConfig(queue_cap=0)
            ServingEngine(self._two_stage()).run(
                10, 40.0, pipeline=PipelineConfig(queue_cap=0)
            )


# ------------------------------------------------- per-frame fanout


class TestFanout:
    def test_accumulator_matches_expand_fanout(self):
        for phi in (0.5, 1.0, 1.5, 2.0, 3.0, 0.7):
            frames = np.arange(200)
            inst = expand_fanout(frames, phi)
            counts = np.bincount(inst, minlength=200)
            acc = AccumulatorFanout(phi)
            mine = np.array([acc.count(f) for f in frames])
            np.testing.assert_array_equal(mine, counts)

    def test_stochastic_is_seeded_and_mean_preserving(self):
        spec = FanoutSpec(mode="stochastic", cv=0.5, correlation=1.0)
        fanouts = {"det": 1.0, "cls_a": 2.0, "cls_b": 3.0}
        a = draw_counts(spec, 4000, fanouts, ["det"], seed=9)
        b = draw_counts(spec, 4000, fanouts, ["det"], seed=9)
        for m in fanouts:
            np.testing.assert_array_equal(a[m], b[m])
        assert a["cls_a"].mean() == pytest.approx(2.0, rel=0.1)
        assert a["cls_b"].mean() == pytest.approx(3.0, rel=0.1)
        # source clamp: a frame always physically exists
        assert a["det"].min() >= 1

    def test_sibling_correlation_tracks_rho(self):
        """correlation=1: a busy frame loads BOTH classifiers (high count
        correlation); correlation=0: independent module jitter."""
        fanouts = {"det": 1.0, "cls_a": 4.0, "cls_b": 4.0}

        def corr(rho):
            spec = FanoutSpec(mode="stochastic", cv=0.8, correlation=rho)
            c = draw_counts(spec, 6000, fanouts, ["det"], seed=3)
            return float(np.corrcoef(c["cls_a"], c["cls_b"])[0, 1])

        assert corr(1.0) > 0.6
        assert abs(corr(0.0)) < 0.15
        assert corr(1.0) > corr(0.5) > corr(0.0) - 0.05

    def test_stochastic_pipeline_run_conserves_frames(self):
        plan = suite_plan(TRAFFIC, 100.0, 2.0)
        cfg = PipelineConfig(fanout=FanoutSpec(mode="stochastic", cv=0.6))
        res = ServingEngine(plan).run(400, 100.0, pipeline=cfg)
        pr = res.pipeline
        # completed + shed + dropped + skipped == all frames
        n_acc = (
            len(res.e2e_latencies) + res.shed + res.dropped + int(pr.skipped.sum())
        )
        assert n_acc == 400
        # same seed, same draw: bit-reproducible
        res2 = ServingEngine(plan).run(400, 100.0, pipeline=cfg)
        np.testing.assert_array_equal(res.e2e_latencies, res2.e2e_latencies)

    def test_spec_validation(self):
        with pytest.raises(ValueError):
            FanoutSpec(mode="bogus")
        with pytest.raises(ValueError):
            FanoutSpec(correlation=1.5)
        with pytest.raises(ValueError):
            FanoutSpec(cv=-1.0)


# ------------------------------------------------- adaptive dummy streaming


class TestPipelineDummyStreaming:
    def test_dummy_padded_plan_hits_modeled_wcl(self):
        """The pipelined injector pads collection up to the provisioned
        collect rate (rate-limited paid-slot pacing): a dummy-padded plan
        under timeout="budget" meets its modeled 2d WCL, like the flat
        frontend's deficit injector."""
        prof = ModuleProfile("M", (Config(32, 0.3),))
        s = schedule_module("M", 10.0, 1.0, prof, Policy.TC)
        assert s is not None and any(a.dummy > 0 for a in s.allocs)
        wl = Workload(AppDAG("app", Leaf("M")), {"M": 10.0}, 1.0)
        plan = Plan(wl, PlannerOptions(), {"M": s}, True, 0.0)
        res = ServingEngine(plan).run(
            600, 10.0, arrivals="poisson", timeout="budget",
            frontend=FrontendConfig(dummies=True), pipeline=True,
        )
        assert res.module_stats["M"].phantom > 0
        assert res.attainment >= 0.99
        assert res.p99 <= plan.workload.slo + 1e-9
        # phantoms never enter the statistics
        assert len(res.e2e_latencies) + res.dropped == 600

    def test_injector_pauses_on_wedged_bounded_stage(self):
        """Regression: a full bounded stage under RR with no flush deadline
        must not be kept alive by the phantom chain — the chain goes dormant
        so the quiescence flush can run, and every frame still completes."""
        from repro.core.dispatch import Alloc
        from repro.core.residual import ModuleSchedule

        c = Config(4, 0.1)
        a = Alloc(c, 2.0, 2 * c.throughput, dummy=5.0)
        s = ModuleSchedule("M", a.rate, 0.0, 0.5, (a,), Policy.RR)
        wl = Workload(AppDAG("app", Leaf("M")), {"M": a.rate}, 1.0)
        plan = Plan(wl, PlannerOptions(policy=Policy.RR), {"M": s}, True, 0.0)
        res = ServingEngine(plan, policy=Policy.RR).run(
            2, a.rate, frontend=FrontendConfig(dummies=True),
            pipeline=PipelineConfig(queue_cap=4),
        )
        assert len(res.e2e_latencies) == 2 and res.dropped == 0

    def test_injector_idle_when_real_traffic_meets_target(self):
        """No dummy rate, real traffic at the provisioned rate on uniform
        arrivals: the adaptive injector stays (nearly) silent."""
        plan = chain_plan(
            [("A", [Config(8, 0.2)], 0.5), ("B", [Config(8, 0.2)], 0.5)],
            40.0, 1.0,
        )
        res = ServingEngine(plan).run(
            400, 40.0, timeout="budget",
            frontend=FrontendConfig(dummies=True), pipeline=True,
        )
        injected = sum(s.phantom for s in res.module_stats.values())
        assert injected <= 8  # at most start-up slack, not a stream

    def test_phantoms_hold_no_queue_capacity(self):
        """A bounded stage whose formation buffers hold only phantoms (which
        arm no deadline) still takes real deliveries; were phantoms to
        count against ``queue_cap``, real deliveries would park for good,
        their upstream machines stay blocked, and the frames drop."""
        from repro.core.dispatch import Machine
        from repro.serving.pipeline import Instance, ModuleStage

        c = Config(8, 0.1)
        machines = [Machine(0, c, c.throughput), Machine(1, c, c.throughput)]
        st = ModuleStage("M", machines, Policy.RR, queue_cap=8, phantom_target=1.0)
        for _ in range(8):  # round-robin: four phantoms a buffer, none full
            st.deliver(Instance(-1), 0.0, lambda *e: None)
        assert st.backlog == 8 and not any(k.full for k in st.cores.values())
        assert st.has_space
        # end to end: the bounded, dummy-streaming run that once wedged
        eng = ServingEngine(suite_plan(TRAFFIC, 100.0, 2.0))
        res = eng.run(
            160, 100.0, arrivals="mmpp", seed=1, timeout="budget",
            frontend=FrontendConfig(dummies=True),
            pipeline=PipelineConfig(queue_cap=6),
        )
        assert res.dropped == 0 and len(res.e2e_latencies) == 160

    def test_trailing_phantoms_do_not_inflate_tail_latency(self):
        """End of stream with no flush deadline: the injector stops with the
        stage's real stream, so the tail batch flushes at its last REAL
        arrival — phantoms must not fill it at the provisioned rate (which
        here would hold the three frames for seconds)."""
        from repro.core.dispatch import Alloc
        from repro.core.residual import ModuleSchedule

        c = Config(8, 0.1)
        a = Alloc(c, 1.0, 2.0, dummy=5.0)  # collect target: 7 req/s
        s = ModuleSchedule("M", a.rate, 0.0, 0.5, (a,), Policy.TC)
        wl = Workload(AppDAG("app", Leaf("M")), {"M": a.rate}, 1.0)
        plan = Plan(wl, PlannerOptions(), {"M": s}, True, 0.0)
        for cfg in (True, REF):
            res = ServingEngine(plan).run(
                3, a.rate, arrivals=np.array([0.0, 0.05, 0.10]),
                frontend=FrontendConfig(dummies=True), pipeline=cfg,
            )
            # one partial batch, flushed at the last real arrival (0.10)
            assert res.module_stats["M"].batches == 1
            np.testing.assert_allclose(
                res.pipeline.finish["M"], 0.10 + 0.1, atol=1e-12
            )


# ------------------------------------------------- event-interleaved clients


class TestInterleavedClients:
    def _plan(self, batched=True):
        if batched:
            return chain_plan(
                [("A", [Config(8, 0.3)], 0.5), ("B", [Config(4, 0.2)], 0.3)],
                80.0, 0.8,
            )
        return chain_plan(
            [("A", [Config(1, 0.05)], 0.2), ("B", [Config(1, 0.05)], 0.2)],
            100.0, 0.5,
        )

    @pytest.mark.parametrize("batched", [False, True])
    def test_closed_loop_obeys_littles_law(self, batched):
        """Every slot alternates one frame's e2e latency and one think, so
        in steady state the issue rate is ``n_clients / (think + mean
        e2e)`` — the latency-oracle model, here an assertion on the
        event-interleaved loop.  Read between the first and third quartiles
        of issue time, away from ramp-up and drain."""
        plan = self._plan(batched)
        n_clients, think = (80, 0.2) if batched else (4, 0.05)
        fe = FrontendConfig(clients=ClosedLoopClients(
            n_clients=n_clients, think_time=think, think_dist="const",
        ))
        res = ServingEngine(plan).run(
            400, 80.0 if batched else 100.0, frontend=fe
        )
        assert res.offered == 400 and res.shed == 0 and res.dropped == 0
        issue = res.pipeline.issue
        t = np.sort(issue)
        lo, hi = len(t) // 4, 3 * len(t) // 4
        rate = (hi - lo) / (t[hi] - t[lo])
        mid = (issue >= t[lo]) & (issue < t[hi])
        e2e = float(np.mean(res.pipeline.e2e[mid]))
        assert rate == pytest.approx(n_clients / (think + e2e), rel=0.02)

    def test_in_flight_bound_serializes_issues(self):
        """One client, one slot: every issue waits for the previous
        completion plus the (constant) think time."""
        plan = chain_plan([("A", [Config(1, 0.2)], 0.5)], 4.0, 1.0)
        fe = FrontendConfig(clients=ClosedLoopClients(
            n_clients=1, max_in_flight=1, think_time=0.05, think_dist="const",
        ))
        res = ServingEngine(plan).run(50, 4.0, frontend=fe)
        assert res.shed == 0 and res.attempts == 50
        np.testing.assert_allclose(res.pipeline.e2e, 0.2, atol=1e-12)
        np.testing.assert_allclose(np.diff(res.pipeline.issue), 0.25, atol=1e-12)

    def test_self_throttle_under_tiny_plan(self):
        """Few clients against a slow plan: the interleaved loop serves
        everything (offered load adapts to completions, quiescent partial
        batches flush causally)."""
        plan = self._plan(batched=True)
        eng = ServingEngine(plan)
        fe = FrontendConfig(clients=ClosedLoopClients(n_clients=4))
        res = eng.run(200, 80.0, frontend=fe, pipeline=True)
        assert res.offered == 200
        assert res.shed == 0 and res.dropped == 0
        assert res.attempts == 200

    def test_retry_and_admission_conserve_frames(self):
        plan = self._plan(batched=True)
        eng = ServingEngine(plan)
        fe = FrontendConfig(
            admission=TokenBucket(rate=40.0, burst=2.0),
            clients=ClosedLoopClients(
                n_clients=64, retry_on_shed=True, max_retries=2, backoff=0.01
            ),
        )
        res = eng.run(400, 80.0, frontend=fe, pipeline=True)
        assert len(res.e2e_latencies) + res.shed + res.dropped == 400
        assert res.attempts >= 400
        # the bucket is half the offered rate, so terminal denials must
        # happen — and with retry_on_shed every terminal denial follows a
        # re-offer, so it classifies as an exhausted-retry DROP (admitted
        # demand the system failed), never a first-sight shed
        assert res.dropped > 0
        assert res.shed == 0


# ------------------------------------------------- per-rank budget floor


class TestPerRankBudgetFloor:
    def _residual_plan(self):
        """Majority machine + dummy-filled residual (Theorem-2 shape): the
        residual's real collection rate is its own small share, so its
        honest fill time is far longer than the whole-module fill time the
        PR-1 floor used."""
        from repro.core.dispatch import Alloc
        from repro.core.residual import ModuleSchedule

        c = Config(32, 0.3)
        maj = Alloc(c, 1.0, c.throughput)
        res = Alloc(c, 1.0, 23.3, dummy=c.throughput - 23.3)
        s = ModuleSchedule("M", maj.rate + 23.3, 0.0, 1.0, (maj, res), Policy.TC)
        wl = Workload(AppDAG("app", Leaf("M")), {"M": s.rate}, 1.6)
        return Plan(wl, PlannerOptions(), {"M": s}, True, 0.0)

    def test_remaining_workloads_rank_structure(self):
        plan = self._residual_plan()
        s = plan.schedules["M"]
        allocs = list(s.allocs)
        w_of = remaining_workloads(allocs)
        machines = expand_machines(allocs)
        assert set(w_of) == {mm.mid for mm in machines}
        ws = [w_of[mm.mid] for mm in machines]
        # ranks are ratio-descending: remaining workload never increases
        assert all(a >= b - 1e-9 for a, b in zip(ws, ws[1:]))
        # the top rank collects at the whole module's real rate
        assert ws[0] == pytest.approx(sum(a.rate for a in allocs))
        # the dummy-filled residual ranks last and collects at its own
        # real share only
        assert ws[-1] == pytest.approx(23.3)

    def test_budget_floor_uses_remaining_workload(self):
        """The fill-time floor of a lower-ranked TC machine is its batch
        over the REMAINING workload w_i, not over the whole module rate."""
        plan = self._residual_plan()
        s = plan.schedules["M"]
        eng = ServingEngine(plan, policy=Policy.TC)
        machines = expand_machines(list(s.allocs))
        w = eng._module_timeout("M", machines, "budget")
        w_of = remaining_workloads(list(s.allocs))
        for mm in machines:
            fill = mm.config.batch / w_of[mm.mid]
            assert w[mm.mid] == pytest.approx(max(s.budget - mm.config.duration, fill))
        # the residual's floor is strictly longer than the whole-rate floor
        low = machines[-1]
        assert w_of[low.mid] < s.rate - 1e-9
        assert w[low.mid] == pytest.approx(32 / 23.3)
        assert w[low.mid] > max(s.budget - 0.3, 32 / s.rate) + 1e-9

    def test_floor_cuts_flush_waste_on_the_residual(self, monkeypatch):
        """Satellite acceptance: collecting at the remaining workload, the
        residual machine executes markedly fewer (fuller) batches than with
        the PR-1 whole-rate floor when traffic runs below provisioning —
        the flush-waste concentration the ROADMAP flagged — while staying
        within the SLO."""
        import repro.serving.engine as engine_mod

        plan = self._residual_plan()
        eng = ServingEngine(plan)
        rate = plan.schedules["M"].rate
        kw = dict(arrivals="poisson", timeout="budget", seed=2,
                  offered_rate=0.35 * rate)
        new = eng.run(1200, rate, **kw)
        # the PR-1 behavior: every TC machine floored at the module rate
        # (remaining_workloads defaulting to s.rate via the .get fallback)
        monkeypatch.setattr(engine_mod, "remaining_workloads", lambda allocs: {})
        old = eng.run(1200, rate, **kw)
        monkeypatch.undo()
        assert new.module_stats["M"].batches < old.module_stats["M"].batches
        assert new.attainment >= 0.98
        assert new.p99 <= plan.workload.slo + 1e-9


# ------------------------------------------------- DAG helper


class TestCriticalMasks:
    def test_series_par_decomposition(self):
        sp = series(Leaf("a"), par(Leaf("b"), Leaf("c")), Leaf("d"))
        soj = {
            "a": np.array([1.0, 1.0]),
            "b": np.array([2.0, 0.5]),
            "c": np.array([1.5, 3.0]),
            "d": np.array([0.5, 0.5]),
        }
        lat, masks = sp_critical_masks(sp, soj)
        np.testing.assert_allclose(lat, [3.5, 4.5])
        np.testing.assert_array_equal(masks["b"], [True, False])
        np.testing.assert_array_equal(masks["c"], [False, True])
        np.testing.assert_array_equal(masks["a"], [True, True])

    def test_nan_branches_lose(self):
        sp = par(Leaf("x"), Leaf("y"))
        soj = {"x": np.array([np.nan, 1.0]), "y": np.array([2.0, np.nan])}
        lat, masks = sp_critical_masks(sp, soj)
        np.testing.assert_allclose(lat, [2.0, 1.0])
        np.testing.assert_array_equal(masks["x"], [False, True])
        np.testing.assert_array_equal(masks["y"], [True, False])
