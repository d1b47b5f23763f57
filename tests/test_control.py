"""Incremental control plane (ISSUE-4): versioned plans, warm-start repair,
and epoch-based plan hot-swap in the serving loop.

Covers: Plan versioning/diff/auditable summary, Planner.replan (tolerance
reuse, repair cost parity vs cold on the 5-app suite, cost-regression guard
fallback, quantized-rate plan cache), the swap invariants (conservation
``completed + shed + dropped == offered`` across epoch boundaries, no
in-flight frame lost on a drain), bit-exact equivalence with the control
loop disabled, per-epoch frontend re-reads (admission rebind, live client
backoff), and the serving-cost time integral.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import Planner
from repro.core import baselines as B
from repro.core.harpagon import PlanDelta
from repro.serving import (
    ControlLoopConfig,
    FrontendConfig,
    QueueDepth,
    ServingEngine,
    TokenBucket,
    serving_cost,
)
from repro.serving.control import EpochRecord
from repro.serving.frontend import ClosedLoopClients, make_admission
from repro.workloads import synth_profiles
from repro.workloads.apps import app_by_name, make_workload

PROFILES = synth_profiles()

SUITE = (
    ("traffic", 100.0, 2.0), ("face", 150.0, 2.5), ("pose", 60.0, 3.0),
    ("caption", 90.0, 2.5), ("actdet", 80.0, 3.0),
)


def suite_plan(name, rate, slo, planner=None):
    pl = planner or Planner(B.HARPAGON)
    plan = pl.plan(make_workload(app_by_name(name), rate, slo), PROFILES)
    assert plan.feasible
    return pl, plan


# ------------------------------------------------- versioned, diffable plans


class TestPlanVersioning:
    def test_cold_plan_is_version_zero(self):
        _, plan = suite_plan("face", 150.0, 2.5)
        assert plan.version == 0
        assert plan.provenance == {}

    def test_replan_bumps_version_and_records_provenance(self):
        pl, plan = suite_plan("face", 150.0, 2.5)
        nr = {m: r * 1.3 for m, r in plan.workload.rates.items()}
        new = pl.replan(plan, nr, PROFILES)
        assert new.version == 1
        assert set(new.provenance) == set(plan.workload.app.modules)
        assert set(new.provenance.values()) <= {"reused", "repaired", "cached", "cold"}
        newer = pl.replan(new, nr, PROFILES)
        assert newer.version == 2

    def test_diff_tracks_machines_rate_and_dummy(self):
        pl, plan = suite_plan("face", 150.0, 2.5)
        nr = {m: r * 1.4 for m, r in plan.workload.rates.items()}
        new = pl.replan(plan, nr, PROFILES)
        delta = plan.diff(new)
        assert isinstance(delta, PlanDelta)
        assert delta.version_from == 0 and delta.version_to == 1
        assert delta.changed_modules  # +40% rate must change machines
        added = sum(d.machines_added for d in delta.modules.values())
        drained = sum(d.machines_drained for d in delta.modules.values())
        assert added > drained  # net growth
        for m, d in delta.modules.items():
            assert d.rate_after == pytest.approx(nr[m])
        assert "add[" in delta.summary()

    def test_diff_rejects_other_app(self):
        _, p1 = suite_plan("face", 150.0, 2.5)
        _, p2 = suite_plan("pose", 60.0, 3.0)
        with pytest.raises(ValueError):
            p1.diff(p2)

    def test_summary_lists_dummy_and_derate_per_alloc(self):
        """Satellite: epoch-by-epoch plan logs are auditable — every alloc
        line carries its dummy rate and headroom derate explicitly."""
        opts = dataclasses.replace(B.HARPAGON, headroom=0.1)
        _, plan = suite_plan("traffic", 100.0, 2.0, Planner(opts))
        text = plan.summary()
        assert f"v{plan.version}" in text
        alloc_lines = [
            l for l in text.splitlines() if "derate=" in l and " x b" not in l
        ]
        n_allocs = sum(len(s.allocs) for s in plan.schedules.values())
        assert len(alloc_lines) == n_allocs
        for line in alloc_lines:
            assert "dummy=" in line and "derate=" in line and "rate=" in line
        # headroom derate is visible, not elided when != 1
        assert any("derate=0.9" in l for l in alloc_lines)


# ------------------------------------------------- warm-start replan


class TestReplan:
    def test_reuse_within_tolerance(self):
        # a small *downward* drift always fits the provisioned capacity; an
        # upward one is reused only when dummy/headroom slack covers it
        pl, plan = suite_plan("pose", 60.0, 3.0)
        nr = {m: r * 0.999 for m, r in plan.workload.rates.items()}
        new = pl.replan(plan, nr, PROFILES, tolerance=0.02)
        assert set(new.provenance.values()) == {"reused"}
        for m in plan.workload.app.modules:
            assert new.schedules[m] is plan.schedules[m]
        assert new.cost == pytest.approx(plan.cost)
        assert plan.diff(new).empty

    def test_shrink_beyond_capacity_is_not_reused(self):
        pl, plan = suite_plan("pose", 60.0, 3.0)
        nr = {m: r * 1.5 for m, r in plan.workload.rates.items()}
        new = pl.replan(plan, nr, PROFILES, tolerance=0.02)
        assert "reused" not in set(new.provenance.values())

    def test_repair_cost_parity_on_suite(self):
        """Acceptance: replan cost within 1% of a cold plan on the 5-app
        suite (mean over up/down ±10% steps; guard-bounded worst case)."""
        ratios = []
        for name, rate, slo in SUITE:
            for f in (0.9, 1.1):
                pl, plan = suite_plan(name, rate, slo, Planner(B.HARPAGON))
                nr = {m: r * f for m, r in plan.workload.rates.items()}
                warm = pl.replan(plan, nr, PROFILES)
                cold = Planner(B.HARPAGON).plan(
                    dataclasses.replace(plan.workload, rates=nr), PROFILES
                )
                assert warm.feasible and cold.feasible
                ratios.append(warm.cost / cold.cost)
        assert np.mean(ratios) <= 1.01
        assert max(ratios) <= 1.06  # single-step worst case is guard-bounded

    def test_cost_guard_falls_back_cold(self):
        pl, plan = suite_plan("caption", 90.0, 2.5)
        nr = {m: r * 1.2 for m, r in plan.workload.rates.items()}
        forced = pl.replan(plan, nr, PROFILES, cost_guard=-0.99)
        cold = Planner(B.HARPAGON).plan(
            dataclasses.replace(plan.workload, rates=nr), PROFILES
        )
        # the guard can only improve on the warm result, never worsen it
        free = Planner(B.HARPAGON).replan(plan, nr, PROFILES, cost_guard=1e9)
        assert forced.cost <= free.cost + 1e-9
        assert forced.cost <= cold.cost * 1.001 + 1e-9

    def test_infeasible_prev_replans_cold(self):
        pl = Planner(B.HARPAGON)
        wl = make_workload(app_by_name("face"), 150.0, 0.001)  # impossible slo
        bad = pl.plan(wl, PROFILES)
        assert not bad.feasible
        nr = {m: r for m, r in wl.rates.items()}
        new = pl.replan(bad, nr, PROFILES)
        assert new.version == 1
        assert set(new.provenance.values()) == {"cold"}

    def test_replan_cache_hits_on_revisited_rates(self):
        """A diurnal walk revisits its rate buckets: the second visit is a
        memo lookup, returned as provenance "cached" with matching cost."""
        pl, plan = suite_plan("face", 150.0, 2.5)
        nr = {m: r * 1.3 for m, r in plan.workload.rates.items()}
        first = pl.replan(plan, nr, PROFILES)
        back = pl.replan(first, plan.workload.rates, PROFILES)
        again = pl.replan(back, nr, PROFILES)
        assert set(again.provenance.values()) == {"cached"}
        assert again.cost == pytest.approx(first.cost)
        assert again.version == back.version + 1


# ------------------------------------------------- hot-swap in the event loop


def _control(interval, **kw):
    kw.setdefault("profiles", PROFILES)
    return ControlLoopConfig(interval=interval, **kw)


class TestHotSwap:
    def test_control_requires_profiles(self):
        _, plan = suite_plan("face", 150.0, 2.5)
        with pytest.raises(ValueError, match="profiles"):
            ServingEngine(plan).run(
                100, 150.0, pipeline=True,
                control=ControlLoopConfig(interval=1.0),
            )

    def test_conservation_across_epoch_boundaries(self):
        """Acceptance: completed + shed + dropped == offered under a
        swapping control loop with admission shedding enabled."""
        _, plan = suite_plan("traffic", 100.0, 2.0)
        n = 1500
        fe = FrontendConfig(dummies=True, admission=TokenBucket(burst=4))
        res = ServingEngine(plan).run(
            n, 100.0, arrivals="mmpp", seed=2, frontend=fe, pipeline=True,
            offered_rate=130.0, control=_control(1.5, margin=0.2),
        )
        assert len(res.e2e_latencies) + res.shed + res.dropped == n
        assert res.epochs is not None and len(res.epochs) >= 3
        assert any(e.swapped for e in res.epochs)

    def test_drain_loses_no_inflight_frame(self):
        """Acceptance: a rate drop drains machines mid-run; every admitted
        frame still completes (drained cores finish their open batch)."""
        _, plan = suite_plan("face", 150.0, 2.5)
        n = 1800
        third = n // 3
        hi = np.arange(2 * third) / 150.0
        lo = hi[-1] + np.arange(1, n - 2 * third + 1) / 40.0
        arr = np.concatenate([hi, lo])
        res = ServingEngine(plan).run(
            n, 150.0, arrivals=arr, frontend=FrontendConfig(dummies=True),
            pipeline=True, control=_control(2.0),
        )
        assert res.dropped == 0 and res.shed == 0
        assert len(res.e2e_latencies) == n
        drained = sum(e.machines_drained for e in res.epochs)
        assert drained > 0  # the drop actually shrank the cluster
        versions = [e.version for e in res.epochs]
        assert versions == sorted(versions)

    @pytest.mark.parametrize("kind", ["uniform", "mmpp"])
    def test_disabled_control_is_bit_exact(self, kind):
        """Acceptance: golden equivalence with the control loop off — and a
        loop whose first epoch falls beyond the stream never fires a swap,
        reproducing the uncontrolled run bit-for-bit."""
        _, plan = suite_plan("traffic", 100.0, 2.0)
        eng = ServingEngine(plan)
        base = eng.run(600, 100.0, arrivals=kind, seed=7, pipeline=True)
        idle = eng.run(
            600, 100.0, arrivals=kind, seed=7, pipeline=True,
            control=_control(1e9),
        )
        np.testing.assert_array_equal(
            np.asarray(base.e2e_latencies), np.asarray(idle.e2e_latencies)
        )
        assert idle.epochs is not None and len(idle.epochs) == 1  # t=0 record
        assert not any(e.swapped for e in idle.epochs)

    def test_warmup_fast_start_cadence(self):
        """warmup=w fires the first replans at interval/2^w, ..., interval/2
        before landing back on the regular grid — a cold-start misprovision
        is repaired within a fraction of the first interval."""
        _, plan = suite_plan("traffic", 100.0, 2.0)
        eng = ServingEngine(plan)
        res = eng.run(
            1200, 100.0, arrivals="uniform", pipeline=True,
            control=_control(4.0, warmup=2),
        )
        ts = [e.t for e in res.epochs]
        # t=0 record, then the ladder 1, 2, 4 and the grid 8
        assert ts[:5] == pytest.approx([0.0, 1.0, 2.0, 4.0, 8.0], abs=0.02)
        plain = eng.run(
            1200, 100.0, arrivals="uniform", pipeline=True,
            control=_control(4.0, warmup=0),
        )
        assert [e.t for e in plain.epochs][:3] == pytest.approx(
            [0.0, 4.0, 8.0], abs=0.02
        )
        with pytest.raises(ValueError, match="warmup"):
            ControlLoopConfig(interval=1.0, warmup=-1)

    def test_epoch_records_are_auditable(self):
        _, plan = suite_plan("pose", 60.0, 3.0)
        res = ServingEngine(plan).run(
            1200, 60.0, arrivals="diurnal", seed=1,
            frontend=FrontendConfig(dummies=True),
            pipeline=True, control=_control(3.0, margin=0.25),
        )
        recs = res.epochs
        assert isinstance(recs[0], EpochRecord)
        assert recs[0].t == 0.0 and recs[0].version == plan.version
        for e in recs[1:]:
            assert e.rate_est > 0 and e.target >= e.rate_est
            assert np.isfinite(e.cost)
            if e.swapped:
                assert e.delta_summary

    def test_serving_cost_integral(self):
        recs = [
            EpochRecord(0.0, 1, 1, 0, 10.0, True, False, {}),
            EpochRecord(5.0, 1, 1, 1, 20.0, True, True, {}),
        ]
        # 10 * 5s + 20 * 5s over 10s = 15
        assert serving_cost(recs, 10.0) == pytest.approx(15.0)


# ------------------------------------------------- per-epoch frontend state


class TestFrontendEpochState:
    def test_admission_rebind_follows_provisioned_rate(self):
        ctrl = make_admission(TokenBucket(burst=4), "app", 100.0)
        assert ctrl._rate == 100.0
        ctrl.admit(0.0)  # consume a token: live state
        tokens = ctrl._tokens
        ctrl.rebind(150.0)
        assert ctrl._rate == 150.0
        assert ctrl._tokens == tokens  # bucket level preserved across rebind
        with pytest.raises(ValueError):
            ctrl.rebind(0.0)

    def test_admission_rebind_pins_explicit_rates(self):
        ctrl = make_admission(TokenBucket(rate=42.0, burst=4), "app", 100.0)
        ctrl.rebind(150.0)
        assert ctrl._rate == 42.0  # operator-pinned policy does not move
        qd = make_admission(QueueDepth(depth=4), "app", 100.0)
        qd.rebind(150.0)
        assert qd.frame_rate == 150.0
        # a queue-depth policy has no rate to move: it reads live backlog
        assert qd.admit_live(0.0, 3) and not qd.admit_live(0.0, 4)

    def test_client_backoff_none_is_live_latency(self):
        cfg = ClosedLoopClients(backoff=None, retry_on_shed=True)
        assert cfg.backoff is None
        with pytest.raises(ValueError):
            ClosedLoopClients(backoff=-1.0)

    def test_closed_loop_with_control_conserves(self):
        _, plan = suite_plan("face", 150.0, 2.5)
        fe = FrontendConfig(
            dummies=True,
            admission=TokenBucket(burst=2),
            clients=ClosedLoopClients(
                n_clients=64, retry_on_shed=True, max_retries=2, backoff=None
            ),
        )
        res = ServingEngine(plan).run(
            600, 150.0, frontend=fe, pipeline=True,
            control=_control(1.0, margin=0.2),
        )
        assert len(res.e2e_latencies) + res.shed + res.dropped == 600
        assert res.attempts >= 600
