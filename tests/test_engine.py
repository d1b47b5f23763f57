"""Serving engine: fanout expansion, SLO attainment, baseline comparison."""
import pytest

from repro.core import Planner
from repro.core import baselines as B
from repro.core.dispatch import Policy
from repro.serving import ControlLoopConfig, FaultConfig, ServingEngine
from repro.workloads import synth_profiles
from repro.workloads.apps import CAPTION, FACE, TRAFFIC, make_workload

PROFILES = synth_profiles()


def test_fanout_instances():
    """traffic: vehicle_cls fanout 2.0, pedestrian_cls 3.0 — batch counts scale."""
    wl = make_workload(TRAFFIC, rate=100.0, slo=2.0)
    plan = Planner(B.HARPAGON).plan(wl, PROFILES)
    assert plan.feasible
    res = ServingEngine(plan).run(1000, 100.0)
    st = res.module_stats
    det = sum(len(g) for g in [st["ssd_detect"].latencies])
    veh = len(st["vehicle_cls"].latencies)
    ped = len(st["pedestrian_cls"].latencies)
    # instances per frame follow the fanout ratios (tail batches may drop some)
    assert veh == pytest.approx(2 * det, rel=0.1)
    assert ped == pytest.approx(3 * det, rel=0.1)


def test_attainment_across_apps():
    for app, rate in ((FACE, 150.0), (CAPTION, 90.0)):
        wl = make_workload(app, rate=rate, slo=2.5)
        plan = Planner(B.HARPAGON).plan(wl, PROFILES)
        if not plan.feasible:
            continue
        res = ServingEngine(plan).run(1200, rate)
        assert res.attainment >= 0.95, (app.name, res.attainment)


def test_rr_engine_worse_or_equal_latency():
    """Serving a TC plan with RR dispatch must not beat TC's worst latency."""
    wl = make_workload(FACE, rate=200.0, slo=2.0)
    plan = Planner(B.HARPAGON).plan(wl, PROFILES)
    assert plan.feasible
    tc = ServingEngine(plan, policy=Policy.TC).run(1500, 200.0)
    rr = ServingEngine(plan, policy=Policy.RR).run(1500, 200.0)
    assert max(tc.e2e_latencies) <= max(rr.e2e_latencies) + 0.15


def _face_plan():
    plan = Planner(B.HARPAGON).plan(make_workload(FACE, rate=150.0, slo=2.5), PROFILES)
    assert plan.feasible
    return plan


@pytest.mark.parametrize("what", ["control", "faults", "miss_report"])
def test_default_run_is_pipelined(what):
    """A plain ``run()`` serves on the pipelined loop: it takes ``control=``
    and ``faults=``, and its result carries the per-frame record."""
    eng = ServingEngine(_face_plan())
    if what == "control":
        res = eng.run(
            400, 150.0, control=ControlLoopConfig(interval=1.0, profiles=PROFILES)
        )
        assert res.epochs and len(res.epochs) > 1
    elif what == "faults":
        res = eng.run(
            400, 150.0, faults=FaultConfig(schedule=((0.5, "crash"),))
        )
        assert res.faults["injected"] == 1
    else:
        res = eng.run(400, 150.0)
        assert res.miss_report().conserved
    assert res.pipeline is not None
    assert len(res.e2e_latencies) + res.shed + res.dropped == res.offered == 400


def test_pipeline_false_rejected():
    """The flat engine is gone: ``pipeline=`` takes True or a config only."""
    eng = ServingEngine(_face_plan())
    for bad in (False, None, 1):
        with pytest.raises(TypeError, match="pipeline"):
            eng.run(10, 150.0, pipeline=bad)
