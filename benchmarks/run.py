"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (derived = the reproduced
metric compared against the paper's claim).

  PYTHONPATH=src python -m benchmarks.run           # all benches
  PYTHONPATH=src python -m benchmarks.run --only fig5 --n 300
  PYTHONPATH=src python -m benchmarks.run --only replay,slo_sweep,shed_sweep --json
    # also writes machine-readable BENCH_serving.json (serving trajectory)
"""
from __future__ import annotations

import argparse
import json
import math
import statistics
import sys
import time

from . import common
from .common import PROFILES, emit, normalized_costs, plan_all, workload_suite

from repro.core import Planner  # noqa: E402
from repro.core import baselines as B  # noqa: E402
from repro.core.bruteforce import optimal_cost  # noqa: E402
from repro.core.dispatch import Policy, module_wcl  # noqa: E402
from repro.core.profiles import TABLE1_M3  # noqa: E402
from repro.core.scheduler import generate_config, generate_config_ktuple  # noqa: E402
from repro.core.residual import apply_dummy  # noqa: E402
from repro.serving import ServingEngine, simulate, simulate_reference  # noqa: E402
from repro.serving.frontend import FrontendConfig, QueueDepth, TokenBucket  # noqa: E402
from repro.workloads.apps import FANOUT  # noqa: E402


def finite_mean(xs):
    f = [x for x in xs if math.isfinite(x)]
    return sum(f) / len(f) if f else math.nan


# ----------------------------------------------------------- Table II
def bench_table2(n: int) -> None:
    """Scheduling methods S1-S4 for M3 @198 req/s, SLO 1 s (paper Table II)."""
    t0 = time.perf_counter()
    _, s1 = generate_config_ktuple(198.0, 1.0, TABLE1_M3, Policy.RR, 2)
    _, s2 = generate_config_ktuple(198.0, 1.0, TABLE1_M3, Policy.TC, 2)
    _, s3 = generate_config(198.0, 1.0, TABLE1_M3, Policy.TC)
    _, s4_allocs = generate_config(198.0, 1.0, TABLE1_M3, Policy.TC)
    dummy, s4 = apply_dummy(198.0, 1.0, TABLE1_M3, s4_allocs, Policy.TC)
    us = (time.perf_counter() - t0) * 1e6 / 4
    cost = lambda a: round(sum(x.cost for x in a), 4)
    derived = (
        f"S1={cost(s1)}|S2={cost(s2)}|S3={cost(s3)}|S4={cost(s4)}|dummy={dummy:g}"
        f"|paper=6.3/5.9/5.3/5.0"
    )
    emit("table2_scheduling", us, derived)


# ----------------------------------------------------------- Fig 5
def bench_fig5_cost(n: int) -> None:
    """Average normalized cost: 4 baselines + optimum (paper Fig. 5)."""
    wls = workload_suite(n)
    t0 = time.perf_counter()
    rows = plan_all(wls, (B.HARPAGON,) + B.BASELINES)
    us = (time.perf_counter() - t0) * 1e6 / max(1, len(wls) * 5)
    norm = normalized_costs(rows, ["harpagon", "nexus", "scrooge", "inferline", "clipper"])
    parts = []
    for k in ("nexus", "scrooge", "inferline", "clipper"):
        xs = norm[k]
        feas = [x for x in xs if math.isfinite(x)]
        parts.append(
            f"{k}={finite_mean(xs):.3f}(max={max(feas):.2f},infeas={len(xs)-len(feas)})"
        )
    derived = "|".join(parts) + "|paper_avg=1.49-2.37"
    emit("fig5_normalized_cost", us, derived)


def bench_fig5_optimal(n: int) -> None:
    """Harpagon vs brute-force optimum: hit rate + worst gap (Fig. 5b)."""
    wls = workload_suite(min(n, 250))
    h = Planner(B.HARPAGON)
    hits = tot = 0
    worst = 1.0
    t0 = time.perf_counter()
    for wl in wls:
        plan = h.plan(wl, PROFILES)
        if not plan.feasible:
            continue
        opt = min(optimal_cost(wl, PROFILES), plan.cost)
        tot += 1
        r = plan.cost / opt
        worst = max(worst, r)
        if r <= 1 + 1e-6:
            hits += 1
    us = (time.perf_counter() - t0) * 1e6 / max(1, tot)
    derived = (
        f"optimal_rate={100*hits/tot:.1f}%|worst=+{100*(worst-1):.1f}%"
        f"|paper=91.5%,+12.1%"
    )
    emit("fig5b_vs_bruteforce", us, derived)


# ----------------------------------------------------------- Fig 6 (ablations)
def bench_fig6_ablations(n: int) -> None:
    wls = workload_suite(n)
    rows = plan_all(wls, (B.HARPAGON,) + B.ABLATIONS)
    names = [o.name for o in B.ABLATIONS]
    norm = normalized_costs(rows, ["harpagon"] + names)
    paper = {
        "harp-2d": 1.796, "harp-dt": 1.441, "harp-1c": 1.665, "harp-2c": 1.030,
        "harp-nb": 1.896, "harp-nhc": 1.232, "harp-nhe": 1.140, "harp-nd": 1.008,
        "harp-0re": 1.010, "harp-1re": 1.006, "harp-tb": 1.353, "harp-q0.01": 1.012,
        "harp-q0.1": 1.306, "harp-nnm": 1.002, "harp-ncd": 1.003,
    }
    for k in names:
        avg = finite_mean(norm[k])
        emit(f"fig6_{k}", 0.0, f"norm_cost={avg:.3f}|paper={paper.get(k, float('nan')):.3f}")


# ----------------------------------------------------------- Fig 7 (dispatch L_wc)
def bench_fig7_dispatch(n: int) -> None:
    """Normalized L_wc of TC vs RR vs DT on fixed configurations (Fig. 7a)."""
    wls = workload_suite(min(n, 400))
    h = Planner(B.HARP_2D)  # configurations derived by Harp-2d, as in the paper
    ratios_rr, ratios_dt = [], []
    t0 = time.perf_counter()
    for wl in wls:
        plan = h.plan(wl, PROFILES)
        if not plan.feasible:
            continue
        for m, s in plan.schedules.items():
            allocs = list(s.allocs)
            tc = module_wcl(allocs, Policy.TC)
            if tc <= 0:
                continue
            ratios_rr.append(module_wcl(allocs, Policy.RR) / tc)
            ratios_dt.append(module_wcl(allocs, Policy.DT_OPT) / tc)
    us = (time.perf_counter() - t0) * 1e6 / max(1, len(ratios_rr))
    derived = (
        f"rr_extra=+{100*(statistics.mean(ratios_rr)-1):.1f}%"
        f"|dt_extra=+{100*(statistics.mean(ratios_dt)-1):.1f}%"
        f"|paper=+90.4%,+42.8%"
    )
    emit("fig7_dispatch_wcl", us, derived)


def bench_fig7_simulation(n: int) -> None:
    """Event-simulated L_wc vs Theorem 1 across planned workloads."""
    wls = workload_suite(60)
    h = Planner(B.HARPAGON)
    gaps = []
    t0 = time.perf_counter()
    checked = 0
    for wl in wls:
        plan = h.plan(wl, PROFILES)
        if not plan.feasible:
            continue
        for m, s in plan.schedules.items():
            allocs = [a for a in s.allocs]
            if any(a.dummy > 0 for a in allocs) or s.dummy:
                continue
            rate = sum(a.rate for a in allocs)
            if rate < 5:
                continue
            sim = simulate(allocs, rate, policy=Policy.TC, n_requests=600)
            if sim.n_requests == 0:
                continue
            theory = module_wcl(allocs, Policy.TC)
            gaps.append(sim.max_latency / theory)
            checked += 1
            if checked >= 40:
                break
        if checked >= 40:
            break
    us = (time.perf_counter() - t0) * 1e6 / max(1, checked)
    derived = f"sim/theory_mean={statistics.mean(gaps):.3f}|max={max(gaps):.3f}|bound~1.0"
    emit("fig7_sim_vs_theorem1", us, derived)


# ----------------------------------------------------------- Fig 8 (multi-config)
def bench_fig8_multiconfig(n: int) -> None:
    wls = workload_suite(n)
    rows = plan_all(wls, (B.HARPAGON, B.HARP_1C, B.HARP_2C))
    norm = normalized_costs(rows, ["harpagon", "harp-1c", "harp-2c"])
    multi = 0
    tot = 0
    for _, plans in rows:
        h = plans["harpagon"]
        if not h.feasible:
            continue
        tot += 1
        if any(len(s.allocs) > 2 for s in h.schedules.values()):
            multi += 1
    derived = (
        f"harp-1c={finite_mean(norm['harp-1c']):.3f}|harp-2c={finite_mean(norm['harp-2c']):.3f}"
        f"|>2cfg={100*multi/max(1,tot):.1f}%|paper=1.665,1.030,32.4%"
    )
    emit("fig8_multiconfig", 0.0, derived)


# ----------------------------------------------------- serving simulator
def bench_slo_sweep(n: int) -> None:
    """SLO attainment / p99 of replayed plans per planner preset under
    uniform vs Poisson vs bursty (MMPP) arrivals, over >= 100 suite
    workloads.  Batches wait to fill (``timeout=None``; tails flush at end
    of stream) so the sweep isolates the arrival-process effect — Harpagon
    runs machines at 100% utilization, where deadline flushing would add a
    second, throughput-collapse effect (see ROADMAP open items)."""
    wls = workload_suite(max(100, min(n, 200)))  # >=100 for coverage, <=200 for runtime
    presets = (B.HARPAGON, B.NEXUS, B.CLIPPER)
    kinds = ("uniform", "poisson", "bursty")
    acc = {(p.name, k): ([], []) for p in presets for k in kinds}
    planned = {p.name: 0 for p in presets}
    t0 = time.perf_counter()
    for wl in wls:
        frame_rate = wl.rates[wl.app.modules[0]] / FANOUT[wl.app.name][wl.app.modules[0]]
        for p in presets:
            plan = Planner(p).plan(wl, PROFILES)
            if not plan.feasible:
                continue
            planned[p.name] += 1
            eng = ServingEngine(plan, policy=p.policy)
            for k in kinds:
                res = eng.run(600, frame_rate, arrivals=k, seed=0)
                att, p99s = acc[(p.name, k)]
                att.append(res.attainment)
                p99s.append(res.p99 / wl.slo)
    us = (time.perf_counter() - t0) * 1e6 / max(1, len(wls))
    for p in presets:
        for k in kinds:
            att, p99s = acc[(p.name, k)]
            emit(
                f"slo_sweep_{p.name}_{k}",
                us,
                f"attain={finite_mean(att):.3f}|p99/slo={finite_mean(p99s):.3f}"
                f"|workloads={planned[p.name]}/{len(wls)}",
                preset=p.name,
                arrivals=k,
                attain=round(finite_mean(att), 4),
                p99_over_slo=round(finite_mean(p99s), 4),
                workloads=planned[p.name],
            )


def bench_shed_sweep(n: int) -> None:
    """Admission control under bursty overload: drive feasible Harpagon plans
    with MMPP arrivals at 1.0x / 1.3x the provisioned rate and compare the
    frontend policies.  Without admission the PR-1 queues (and p99) grow with
    the run length; token-bucket / queue-depth shedding bounds p99 at the
    price of an explicit, reported shed rate.

    A second leg re-runs the 1.3x overload point through the pipelined
    co-simulation with SLO-miss forensics attached
    (`ServeResult.miss_report`): every missed or shed frame classified
    into exactly one cause, so the policy comparison also reports *what
    kind* of miss each admission policy trades into (`shed_causes_*`
    rows, conservation-checked)."""
    wls = workload_suite(max(60, min(n, 120)))
    fes = (
        ("none", FrontendConfig(dummies=True)),
        ("token_bucket", FrontendConfig(dummies=True, admission=TokenBucket(burst=4))),
        ("queue_depth", FrontendConfig(dummies=True, admission=QueueDepth(depth=8))),
    )
    loads = (1.0, 1.3)
    acc = {(a, l): ([], [], []) for a, _ in fes for l in loads}  # att, p99, shed
    planned = 0
    forensic: list = []  # first few (plan, frame_rate) for the causes leg
    t0 = time.perf_counter()
    for wl in wls:
        frame_rate = wl.rates[wl.app.modules[0]] / FANOUT[wl.app.name][wl.app.modules[0]]
        plan = Planner(B.HARPAGON).plan(wl, PROFILES)
        if not plan.feasible:
            continue
        planned += 1
        if len(forensic) < 10:
            forensic.append((plan, frame_rate))
        eng = ServingEngine(plan)
        for name, fe in fes:
            for load in loads:
                res = eng.run(
                    600, frame_rate, arrivals="mmpp", seed=0,
                    timeout="budget", frontend=fe,
                    offered_rate=load * frame_rate,
                )
                att, p99s, sheds = acc[(name, load)]
                att.append(res.attainment)
                p99s.append(res.p99 / wl.slo)
                sheds.append(res.shed / max(1, res.offered))
        if planned >= 40:
            break
    us = (time.perf_counter() - t0) * 1e6 / max(1, planned)
    for name, _ in fes:
        for load in loads:
            att, p99s, sheds = acc[(name, load)]
            emit(
                f"shed_sweep_{name}_{load:g}x",
                us,
                f"attain={finite_mean(att):.3f}|p99/slo={finite_mean(p99s):.3f}"
                f"|shed={100*finite_mean(sheds):.1f}%|workloads={planned}",
                admission=name,
                load=load,
                attain=round(finite_mean(att), 4),
                p99_over_slo=round(finite_mean(p99s), 4),
                shed_rate=round(finite_mean(sheds), 4),
                workloads=planned,
            )

    # -- miss-cause forensics leg (1.3x overload, pipelined co-simulation)
    cause_acc: dict[str, dict[str, int]] = {name: {} for name, _ in fes}
    totals = {name: [0, 0] for name, _ in fes}  # [misses, offered]
    t0 = time.perf_counter()
    for plan, frame_rate in forensic:
        eng = ServingEngine(plan)
        for name, fe in fes:
            res = eng.run(
                600, frame_rate, arrivals="mmpp", seed=0,
                timeout="budget", frontend=fe,
                offered_rate=1.3 * frame_rate, pipeline=True,
            )
            rep = res.miss_report()
            if not rep.conserved:
                print(
                    f"# FAILURE: miss-cause conservation violated for "
                    f"{plan.workload.app.name}/{name}: {rep.counts} vs "
                    f"{rep.offered} offered, {rep.completed_in_slo} in SLO",
                    file=sys.stderr,
                )
                raise SystemExit(1)
            for k, v in rep.counts.items():
                cause_acc[name][k] = cause_acc[name].get(k, 0) + v
            totals[name][0] += rep.total
            totals[name][1] += rep.offered
    us = (time.perf_counter() - t0) * 1e6 / max(1, len(forensic))
    for name, _ in fes:
        counts = cause_acc[name]
        misses, offered = totals[name]
        dominant = (
            max(counts.items(), key=lambda kv: (kv[1], kv[0]))[0]
            if counts
            else "none"
        )
        emit(
            f"shed_causes_{name}",
            us,
            f"dominant={dominant}|misses={misses}/{offered}"
            f"|workloads={len(forensic)}|load=1.3x",
            admission=name,
            load=1.3,
            dominant=dominant,
            misses=misses,
            offered=offered,
            causes={k: counts[k] for k in sorted(counts)},
            workloads=len(forensic),
        )


def bench_pipeline_sweep(n: int) -> None:
    """Pipelined end-to-end p99 vs the analytic critical-path WCL sum, per
    latency splitter.  The multi-module co-simulation (engine
    ``pipeline=True``) is the first honest end-to-end check of the splitter
    budgets: every frame traverses the DAG through real batch formation, so
    p99/WCL-sum near 1.0 means the per-module budget assignment survives
    cross-stage hand-off; the mean sits below it by the batch-collection
    slack."""
    from repro.core.harpagon import PlannerOptions
    from repro.workloads.apps import app_by_name, make_workload

    seeds = (
        ("traffic", 100.0, 2.0), ("face", 150.0, 2.5), ("pose", 60.0, 3.0),
        ("caption", 90.0, 2.5), ("actdet", 80.0, 3.0),
    )
    n_frames = max(200, min(n, 600))
    for split in ("lc", "throughput", "even", "quantized"):
        ratios, means, attains, apps = [], [], [], 0
        t0 = time.perf_counter()
        for name, rate, slo in seeds:
            wl = make_workload(app_by_name(name), rate, slo)
            opts = PlannerOptions(name=f"split-{split}", split=split)
            plan = Planner(opts).plan(wl, PROFILES)
            if not plan.feasible:
                continue
            res = ServingEngine(plan).run(n_frames, rate, pipeline=True)
            wcl_sum = plan.e2e_latency
            ratios.append(res.p99 / wcl_sum)
            means.append(
                sum(res.e2e_latencies) / max(1, len(res.e2e_latencies)) / wcl_sum
            )
            attains.append(res.attainment)
            apps += 1
        us = (time.perf_counter() - t0) * 1e6 / max(1, apps)
        emit(
            f"pipeline_sweep_{split}",
            us,
            f"p99/wcl={finite_mean(ratios):.3f}|mean/wcl={finite_mean(means):.3f}"
            f"|attain={finite_mean(attains):.3f}|apps={apps}/5",
            split=split,
            p99_over_wcl=round(finite_mean(ratios), 4),
            mean_over_wcl=round(finite_mean(means), 4),
            attain=round(finite_mean(attains), 4),
            apps=apps,
        )


def bench_diurnal_sweep(n: int) -> None:
    """Incremental control plane vs static peak provisioning under diurnal
    arrivals (ISSUE-4 acceptance).

    For each 5-app suite seed, one full diurnal period (sinusoidal intensity
    ``1 + 0.8 sin``, peak = 1.8x mean) is served two ways, both planned
    against a derated internal SLO (``slo / 1.25`` — transient-absorbing
    slack, attainment measured at the real SLO) with dummy streaming on and
    ``timeout="budget"`` deadline flushing re-enabled behind the
    burst-aware deadline flag (``FrontendConfig(burst_deadline=True)``
    closes the PR-4 partial-flush collapse downstream of batched stages;
    without it this sweep had to run deadline-less):

    * **static**: one plan provisioned for the diurnal *peak* rate;
    * **replan**: initial plan at the mean rate + the epoch-based control
      loop (windowed trend-forecast rate estimation, ``Planner.replan``
      warm-start repair, live hot-swap) at each replan interval.

    Serving cost for the replanned arm is the time-integral of the active
    plan's cost over the run (`repro.serving.control.serving_cost`).
    Acceptance: at the finer interval, periodic replanning is >= 1.2x
    cheaper at (near-)equal attainment.  A second micro-row times
    ``Planner.replan`` along a two-period epoch walk against a cold
    ``plan()`` at every step: >= 5x faster at matched (<=1%) mean cost.
    """
    import dataclasses as _dc

    import numpy as np

    from repro.core.harpagon import Plan  # noqa: F401  (doc pointer)
    from repro.serving import ControlLoopConfig, FrontendConfig, serving_cost
    from repro.serving.arrivals import trace_arrivals
    from repro.workloads.apps import app_by_name, make_workload

    seeds = (
        ("traffic", 100.0, 2.0), ("face", 150.0, 2.5), ("pose", 60.0, 3.0),
        ("caption", 90.0, 2.5), ("actdet", 80.0, 3.0),
    )
    derate = 1.25
    peak = 1.8
    n_frames = 2400 if SMOKE else max(6000, min(n * 8, 9000))
    intervals = (12, 48)  # replan interval = period / divisor
    agg = {d: ([], [], []) for d in intervals}  # ratio, attain_rp, attain_st
    for name, rate, slo in seeds:
        period = n_frames / rate
        arr = trace_arrivals(n_frames, rate, seed=0, period=period)
        fe = FrontendConfig(dummies=True, burst_deadline=True)
        slo_plan = slo / derate
        wl = make_workload(app_by_name(name), rate, slo_plan)
        plan = Planner(B.HARPAGON).plan(wl, PROFILES)
        wl_pk = make_workload(app_by_name(name), rate * peak, slo_plan)
        plan_pk = Planner(B.HARPAGON).plan(wl_pk, PROFILES)
        if not plan.feasible or not plan_pk.feasible:
            emit(f"diurnal_sweep_{name}", 0.0, "infeasible", app=name, feasible=False)
            continue
        res_pk = ServingEngine(plan_pk).run(
            n_frames, rate * peak, arrivals=arr, frontend=fe, pipeline=True,
            timeout="budget",
        )
        att = lambda r: float(
            (np.asarray(r.e2e_latencies) <= slo + 1e-9).sum() / max(1, r.offered)
        )
        att_st = att(res_pk)
        for div in intervals:
            t0 = time.perf_counter()
            ctrl = ControlLoopConfig(
                interval=period / div, profiles=PROFILES, margin=0.25
            )
            res = ServingEngine(plan).run(
                n_frames, rate, arrivals=arr, frontend=fe, pipeline=True,
                control=ctrl, timeout="budget",
            )
            cost_rp = serving_cost(res.epochs, float(arr[-1]))
            ratio = plan_pk.cost / cost_rp
            a_rp = att(res)
            swaps = sum(1 for e in res.epochs if e.swapped)
            agg[div][0].append(ratio)
            agg[div][1].append(a_rp)
            agg[div][2].append(att_st)
            us = (time.perf_counter() - t0) * 1e6
            emit(
                f"diurnal_sweep_{name}_P{div}",
                us,
                f"cost_ratio={ratio:.2f}|replan_attain={a_rp:.3f}"
                f"|static_attain={att_st:.3f}|replan_cost={cost_rp:.2f}"
                f"|static_cost={plan_pk.cost:.2f}|swaps={swaps}",
                app=name,
                interval_div=div,
                cost_ratio=round(ratio, 4),
                replan_attain=round(a_rp, 4),
                static_attain=round(att_st, 4),
                replan_cost=round(cost_rp, 4),
                static_cost=round(plan_pk.cost, 4),
                swaps=swaps,
            )
    for div in intervals:
        ratios, a_rp, a_st = agg[div]
        emit(
            f"diurnal_sweep_agg_P{div}",
            0.0,
            f"cost_ratio={finite_mean(ratios):.2f}|replan_attain={finite_mean(a_rp):.3f}"
            f"|static_attain={finite_mean(a_st):.3f}|target>=1.2x",
            interval_div=div,
            cost_ratio=round(finite_mean(ratios), 4),
            replan_attain=round(finite_mean(a_rp), 4),
            static_attain=round(finite_mean(a_st), 4),
        )

    # --- Planner.replan vs cold plan(): a two-period diurnal epoch walk ---
    t_warm = t_cold = 0.0
    cost_ratios = []
    epochs = 24 if SMOKE else 48
    for name, rate, slo in seeds:
        pl = Planner(B.HARPAGON)
        wl = make_workload(app_by_name(name), rate, slo)
        cur = pl.plan(wl, PROFILES)
        if not cur.feasible:
            continue
        for k in range(1, 2 * epochs + 1):
            f = 1.0 + 0.35 * math.sin(2.0 * math.pi * k / epochs)
            nr = {m: r * f for m, r in wl.rates.items()}
            t0 = time.perf_counter()
            warm = pl.replan(cur, nr, PROFILES)
            t_warm += time.perf_counter() - t0
            t0 = time.perf_counter()
            cold = Planner(B.HARPAGON).plan(
                _dc.replace(wl, rates=nr), PROFILES
            )
            t_cold += time.perf_counter() - t0
            if warm.feasible and cold.feasible:
                cost_ratios.append(warm.cost / cold.cost)
            cur = warm
    speedup = t_cold / max(t_warm, 1e-12)
    if not cost_ratios:
        emit("diurnal_replan_speed", 0.0, "infeasible: no warm/cold step pair")
        return
    emit(
        "diurnal_replan_speed",
        t_warm * 1e6 / max(1, len(cost_ratios)),
        f"speedup={speedup:.1f}x|warm/cold_cost={finite_mean(cost_ratios):.4f}"
        f"|worst={max(cost_ratios):.4f}|steps={len(cost_ratios)}"
        f"|target>=5x,cost<=1.01",
        speedup=round(speedup, 2),
        cost_ratio_mean=round(finite_mean(cost_ratios), 4),
        cost_ratio_worst=round(max(cost_ratios), 4),
        steps=len(cost_ratios),
    )


def bench_pipeline_speed(n: int) -> None:
    """Macro-event pipeline core vs the event-by-event reference loop
    (ISSUE-5 acceptance): a multi-module app at >= 10^5 frames must replay
    >= 5x faster on the default path (segment fast-path to the vectorized
    flat kernel) with BIT-identical per-frame results.  Under ``--smoke``
    the stream shrinks to 2*10^4 frames and a speedup below 3x, a fast-path
    frame rate below 10^5 frames/s, or any result disagreement FAILS the
    run — the pipeline hot-path regression gate.

    A second matrix leg replays the dummy-streaming ``burst_deadline``
    configuration (budget deadlines + phantom fill — the PR-5 partial-flush
    collapse surface) reference-vs-default and gates on agreement alone:
    that path stays on the event loop, so there is no speed target, but a
    divergence between the two drivers is exactly the regression the plain
    leg cannot see.

    A third leg re-times the fast path with sampled observability attached
    (``ObservabilityConfig(sample=0.1)``): tracing must stay bit-exact and
    — under ``--smoke``, a hard gate — inside a 10% overhead envelope,
    because the telemetry hooks are column-level on the fast path and
    guarded single branches on the event loop.  Smoke mode also exports a
    Perfetto trace from a small diurnal control-plane run
    (``trace_smoke.json``, the CI artifact) and fails if the export is not
    loadable non-empty JSON."""
    import numpy as np

    from repro.serving import ControlLoopConfig, ObservabilityConfig
    from repro.serving.arrivals import trace_arrivals
    from repro.serving.pipeline import PipelineConfig
    from repro.workloads.apps import app_by_name, make_workload

    rate, slo = 150.0, 2.5
    wl = make_workload(app_by_name("face"), rate, slo)
    plan = Planner(B.HARPAGON).plan(wl, PROFILES)
    assert plan.feasible
    eng = ServingEngine(plan)
    n_frames = 20_000 if SMOKE else 100_000
    ref, us_ref = common.timed(
        lambda: eng.run(
            n_frames, rate, arrivals="poisson",
            pipeline=PipelineConfig(reference=True),
        ),
        repeat=1 if SMOKE else 2,
    )
    fast, us_fast = common.timed(
        lambda: eng.run(n_frames, rate, arrivals="poisson", pipeline=True),
        repeat=3,
    )
    t_ref, t_fast = us_ref / 1e6, us_fast / 1e6
    agree = bool(
        np.array_equal(ref.pipeline.e2e, fast.pipeline.e2e, equal_nan=True)
        and all(
            np.array_equal(
                ref.pipeline.finish[m], fast.pipeline.finish[m], equal_nan=True
            )
            for m in ref.pipeline.modules
        )
    )
    speedup = t_ref / t_fast
    # the reference loop's event throughput: how much per-event Python the
    # fast path is buying down (>= 2 instances + free/flush per frame)
    ref_fps = n_frames / t_ref
    fast_fps = n_frames / t_fast
    emit(
        "pipeline_speed",
        t_fast * 1e6,
        f"reference={t_ref:.2f}s|fast={t_fast:.3f}s|speedup={speedup:.1f}x"
        f"|frames/s={fast_fps:,.0f}|n={n_frames:g}|agree={agree}"
        f"|target>={'3x(smoke)' if SMOKE else '5x'}",
        reference_s=round(t_ref, 4),
        fast_s=round(t_fast, 4),
        speedup=round(speedup, 2),
        n_frames=n_frames,
        ref_frames_per_s=round(ref_fps, 1),
        fast_frames_per_s=round(fast_fps, 1),
        agree=agree,
    )
    if SMOKE and (not agree or speedup < 3.0 or fast_fps < 100_000):
        print(
            f"# SMOKE FAILURE: pipeline speedup {speedup:.1f}x < 3x, "
            f"fast path {fast_fps:,.0f} frames/s < 100,000, or result "
            f"disagreement (agree={agree})",
            file=sys.stderr,
        )
        raise SystemExit(1)

    # burst-deadline matrix leg: same reference-vs-default agreement gate
    # on the dummy-streaming budget-deadline path (event loop both ways)
    fe = FrontendConfig(dummies=True, burst_deadline=True)
    n_burst = 6_000 if SMOKE else 20_000
    ref_b, us_ref_b = common.timed(
        lambda: eng.run(
            n_burst, rate, arrivals="poisson", frontend=fe, timeout="budget",
            pipeline=PipelineConfig(reference=True),
        ),
        repeat=1,
    )
    fast_b, us_fast_b = common.timed(
        lambda: eng.run(
            n_burst, rate, arrivals="poisson", frontend=fe, timeout="budget",
            pipeline=True,
        ),
        repeat=1,
    )
    agree_b = bool(
        np.array_equal(ref_b.pipeline.e2e, fast_b.pipeline.e2e, equal_nan=True)
        and all(
            np.array_equal(
                ref_b.pipeline.finish[m], fast_b.pipeline.finish[m],
                equal_nan=True,
            )
            for m in ref_b.pipeline.modules
        )
    )
    emit(
        "pipeline_speed_burst",
        us_fast_b,
        f"reference={us_ref_b / 1e6:.2f}s|default={us_fast_b / 1e6:.2f}s"
        f"|n={n_burst:g}|agree={agree_b}|gate=agreement",
        n_frames=n_burst,
        agree=agree_b,
    )
    if SMOKE and not agree_b:
        print(
            "# SMOKE FAILURE: burst_deadline pipeline leg disagrees "
            "reference vs default",
            file=sys.stderr,
        )
        raise SystemExit(1)

    # observability overhead leg: sampled tracing on the same plain-path
    # run — must stay bit-exact and (smoke gate) within 10% of untraced
    traced, us_obs = common.timed(
        lambda: eng.run(
            n_frames, rate, arrivals="poisson", pipeline=True,
            observability=ObservabilityConfig(sample=0.1),
        ),
        repeat=3,
    )
    agree_t = bool(
        np.array_equal(fast.pipeline.e2e, traced.pipeline.e2e, equal_nan=True)
        and all(
            np.array_equal(
                fast.pipeline.finish[m], traced.pipeline.finish[m],
                equal_nan=True,
            )
            for m in fast.pipeline.modules
        )
    )
    overhead = us_obs / us_fast
    emit(
        "pipeline_speed_traced",
        us_obs,
        f"traced={us_obs / 1e6:.3f}s|overhead={overhead:.3f}x"
        f"|agree={agree_t}|sample=0.1|gate<=1.10x(smoke)",
        traced_s=round(us_obs / 1e6, 4),
        overhead=round(overhead, 3),
        agree=agree_t,
        n_frames=n_frames,
    )
    if SMOKE and (not agree_t or overhead > 1.10):
        print(
            f"# SMOKE FAILURE: sampled tracing overhead {overhead:.3f}x "
            f"> 1.10x or result disagreement (agree={agree_t})",
            file=sys.stderr,
        )
        raise SystemExit(1)

    if SMOKE:
        # Perfetto artifact for CI: a small diurnal control-plane run with
        # full tracing, exported as trace_smoke.json — the gate is only
        # that the export loads as non-empty trace-event JSON
        n_t = 3_000
        period = n_t / rate
        arr = trace_arrivals(n_t, rate, seed=0, period=period)
        res_t = eng.run(
            n_t, rate, arrivals=arr, timeout="budget",
            frontend=FrontendConfig(dummies=True, burst_deadline=True),
            pipeline=True,
            control=ControlLoopConfig(
                interval=period / 6, profiles=PROFILES, margin=0.25
            ),
            observability=True,
        )
        path = res_t.trace.export("trace_smoke.json")
        with open(path) as f:
            doc = json.load(f)
        if not doc.get("traceEvents"):
            print(
                "# SMOKE FAILURE: trace_smoke.json has no traceEvents",
                file=sys.stderr,
            )
            raise SystemExit(1)
        print(
            f"# wrote {len(doc['traceEvents'])} Perfetto trace events to "
            f"{path} ({len(res_t.epochs)} control epochs)",
            file=sys.stderr,
        )


def bench_wallclock_gap(n: int) -> None:
    """Analytic-vs-measured service-time gap per arch at b in {1, 8, 32} —
    the simulator-to-serving calibration row (ISSUE-6).

    Full mode times real jitted reduced-model forwards on CPU through
    `LiveServiceTime` (warmup retires the compile transient) and reports
    the measured/analytic duration ratio's mean and p99 per batch size;
    the analytic side is the same roofline profile the planner consumes,
    so the row tracks exactly the divergence *Beyond Inference*-style host
    overheads introduce.  Under ``--smoke`` the measurements are replayed
    from a seeded recorded trace through `TraceServiceTime` (deterministic,
    no jax compile) — CI exercises the trace backend and the gap
    accounting at zero compile cost."""
    import numpy as np

    from repro.core.dispatch import Config as _Cfg
    from repro.core.dispatch import Machine as _Machine
    from repro.profiling import arch_profile
    from repro.serving import LiveServiceTime, TraceServiceTime

    from repro.configs import get_config

    archs = ("smollm-360m", "gemma3-1b")
    batches = (1, 8, 32)
    seq = 32
    repeats = 5 if SMOKE else max(5, min(n // 10, 20))
    for arch in archs:
        cfg = get_config(arch, smoke=True)
        prof = arch_profile(cfg, seq=seq, batches=batches)
        analytic = {
            b: min(c.duration for c in prof.configs if c.batch == b)
            for b in batches
        }
        if SMOKE:
            # recorded-trace stand-in: a fixed calibration offset plus
            # seeded lognormal scatter, drawn through the trace backend
            rng = np.random.default_rng(0)
            samples = {
                (arch, b): [
                    analytic[b] * 1.2 * float(np.exp(0.05 * rng.standard_normal()))
                    for _ in range(repeats + 1)
                ]
                for b in batches
            }
            src = TraceServiceTime(samples)
            backend = "trace"
        else:
            from repro.launch.serve import build_executors

            src = LiveServiceTime(
                build_executors([arch], seq=seq, smoke=True), warmup=1,
                cache=False,
            )
            backend = "live"
        parts = []
        data = {"backend": backend, "seq": seq}
        for b in batches:
            mach = _Machine(
                mid=0,
                config=_Cfg(batch=b, duration=analytic[b], hardware="tpu-v5e"),
                rate=1.0,
            )
            draws = np.array(
                [src.duration(arch, mach, b) for _ in range(repeats + 1)]
            )[1:]  # first draw = warmup (live) / align the trace cursor
            gaps = draws / analytic[b]
            g_mean, g_p99 = float(gaps.mean()), float(np.percentile(gaps, 99))
            parts.append(f"b{b}={g_mean:.2f}x/p99={g_p99:.2f}x")
            data[f"gap_mean_b{b}"] = round(g_mean, 4)
            data[f"gap_p99_b{b}"] = round(g_p99, 4)
        emit(
            f"wallclock_gap_{arch}",
            0.0,
            "|".join(parts) + f"|backend={backend}",
            **data,
        )


def _plan_fingerprint(plan) -> tuple:
    """Bit-level plan identity: feasibility, cost, and every schedule."""
    return (
        plan.feasible,
        plan.cost,
        tuple(sorted((m, repr(s)) for m, s in plan.schedules.items())),
    )


def bench_planner_speed(n: int) -> None:
    """Planner.plan wall-clock over the workload suite — the paper's
    "millisecond-level planning runtime" claim, tracked as a trajectory row.

    Times both the batched numpy cascade (`vectorized=True`, the default)
    and the scalar `wcl_memo` oracle it replaced, and checks the two
    produce bit-equal plans on every workload.  Under ``--smoke`` (CI)
    this is a hard gate: vectorized ms/plan above the 5 ms paper budget,
    or any plan disagreement, FAILS the run (exit 1)."""
    import dataclasses

    wls = workload_suite(max(60, min(n, 60 if SMOKE else 200)))
    vec = Planner(B.HARPAGON)
    sca = Planner(dataclasses.replace(B.HARPAGON, vectorized=False))
    t0 = time.perf_counter()
    plans = [vec.plan(wl, PROFILES) for wl in wls]
    t = time.perf_counter() - t0
    t1 = time.perf_counter()
    plans_s = [sca.plan(wl, PROFILES) for wl in wls]
    t_s = time.perf_counter() - t1
    agree = all(
        _plan_fingerprint(a) == _plan_fingerprint(b)
        for a, b in zip(plans, plans_s)
    )
    feas = sum(1 for p in plans if p.feasible)
    ms = 1e3 * t / len(wls)
    ms_s = 1e3 * t_s / len(wls)
    emit(
        "planner_speed",
        t * 1e6 / len(wls),
        f"plan={ms:.2f}ms|scalar={ms_s:.2f}ms|speedup={ms_s / ms:.1f}x"
        f"|agree={agree}|feasible={feas}/{len(wls)}|paper=5ms",
        ms_per_plan=round(ms, 3),
        scalar_ms_per_plan=round(ms_s, 3),
        speedup=round(ms_s / ms, 2),
        agree=bool(agree),
        workloads=len(wls),
        feasible=feas,
    )
    if SMOKE and (not agree or ms > 5.0):
        print(
            f"# SMOKE FAILURE: planner {ms:.2f}ms/plan > 5ms budget or "
            f"vectorized/scalar plan disagreement (agree={agree})",
            file=sys.stderr,
        )
        raise SystemExit(1)


def bench_dp_splitter(n: int) -> None:
    """Exact quantized-budget DP splitter (``split="dp"``) vs the four
    heuristic splitters and the brute-force optimum (ROADMAP's fifth
    splitter).

    On the feasible sub-suite (workloads where both the DP grid and the
    heuristics admit a plan) reports each splitter's mean cost normalized
    to the brute-force optimum and the DP's optimality rate (fraction of
    workloads where its plan cost matches the optimum to 1e-6 — the
    paper's Fig. 5b framing puts Harpagon's own cascade at 91.5%).  Under
    ``--smoke`` a DP optimality rate below 91.5% FAILS the run: the DP
    shares the brute-force curves, so falling under the cascade's own
    rate means the budget-recovery walk regressed.

    Also times the module cost-curve pass cold vs warm: curves are
    cached across workloads by quantized (rate, slo) bucket
    (`bruteforce.curve_cache_clear`), so a replayed suite re-prices
    nothing — the ``curve_speedup`` column tracks that win."""
    import dataclasses

    from repro.core.bruteforce import curve_cache_clear, curve_cache_stats

    wls = workload_suite(min(n, 30 if SMOKE else 120))
    splits = ("dp", "lc", "throughput", "even", "quantized")
    planners = {
        s: Planner(dataclasses.replace(B.HARPAGON, split=s)) for s in splits
    }
    # cold vs warm curve pass over the same suite (the cache's whole point:
    # the second pass shares every curve the first one priced)
    curve_cache_clear()
    t0 = time.perf_counter()
    for wl in wls:
        optimal_cost(wl, PROFILES)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    for wl in wls:
        optimal_cost(wl, PROFILES)
    t_warm = time.perf_counter() - t0
    curve_speedup = t_cold / max(t_warm, 1e-12)
    cache = curve_cache_stats()

    sums = {s: 0.0 for s in splits}
    hits = tot = 0
    t0 = time.perf_counter()
    for wl in wls:
        opt_grid = optimal_cost(wl, PROFILES)
        if not math.isfinite(opt_grid):
            continue
        plans = {s: planners[s].plan(wl, PROFILES) for s in splits}
        if not all(p.feasible for p in plans.values()):
            continue
        # normalize against the best point any method found (continuous
        # splits can dip a hair below the budget grid); the DP's hit is
        # judged against the grid optimum it shares with brute force
        best = min([opt_grid] + [p.cost for p in plans.values()])
        tot += 1
        for s in splits:
            sums[s] += plans[s].cost / best
        if plans["dp"].cost <= opt_grid * (1 + 1e-6):
            hits += 1
    us = (time.perf_counter() - t0) * 1e6 / max(1, tot)
    rate = 100.0 * hits / max(1, tot)
    norm = {s: sums[s] / max(1, tot) for s in splits}
    emit(
        "dp_splitter_optimality",
        us,
        f"dp={norm['dp']:.4f}|lc={norm['lc']:.4f}|thr={norm['throughput']:.4f}"
        f"|even={norm['even']:.4f}|quant={norm['quantized']:.4f}"
        f"|optimal_rate={rate:.1f}%|feasible={tot}/{len(wls)}"
        f"|curve_cold={t_cold*1e3:.0f}ms|curve_warm={t_warm*1e3:.1f}ms"
        f"|curve_speedup={curve_speedup:.0f}x"
        f"|gate>=91.5%",
        optimal_rate=round(rate, 2),
        feasible=tot,
        workloads=len(wls),
        curve_cold_ms=round(t_cold * 1e3, 2),
        curve_warm_ms=round(t_warm * 1e3, 3),
        curve_speedup=round(curve_speedup, 1),
        curve_hits=cache["hits"],
        curve_misses=cache["misses"],
        **{f"norm_{s}": round(norm[s], 5) for s in splits},
    )
    if SMOKE and rate < 91.5:
        print(
            f"# SMOKE FAILURE: dp splitter optimality {rate:.1f}% < 91.5%",
            file=sys.stderr,
        )
        raise SystemExit(1)


def bench_replay_speed(n: int) -> None:
    """Vectorized replay kernel vs the frozen pure-Python loop at 10^6
    requests on one planned module (acceptance: >= 5x).  Under ``--smoke``
    (CI) the stream shrinks to 2*10^5 requests and a speedup below the
    smoke floor (3x, conservative for noisy shared runners) FAILS the run —
    the hot-path regression gate."""
    profile = PROFILES["ssd_detect"]
    ok, allocs = generate_config(500.0, 2.0, profile, Policy.TC)
    assert ok
    rate = sum(a.rate for a in allocs)
    n_req = 200_000 if SMOKE else 1_000_000
    # best-of-repeats so a transiently loaded machine can't skew the ratio
    ref, us_ref = common.timed(
        lambda: simulate_reference(allocs, rate, n_requests=n_req), repeat=2
    )
    new, us_vec = common.timed(
        lambda: simulate(allocs, rate, n_requests=n_req), repeat=3
    )
    t_ref, t_vec = us_ref / 1e6, us_vec / 1e6
    agree = abs(ref.max_latency - new.max_latency) < 1e-9 and ref.n_requests == new.n_requests
    speedup = t_ref / t_vec
    emit(
        "replay_vectorized_speedup",
        t_vec * 1e6,
        f"python={t_ref:.2f}s|vectorized={t_vec:.3f}s|speedup={speedup:.1f}x"
        f"|n={n_req:g}|agree={agree}|target>={'3x(smoke)' if SMOKE else '5x'}",
        python_s=round(t_ref, 4),
        vectorized_s=round(t_vec, 4),
        speedup=round(speedup, 2),
        n_requests=n_req,
        agree=bool(agree),
    )
    if SMOKE and (not agree or speedup < 3.0):
        print(
            f"# SMOKE FAILURE: replay speedup {speedup:.1f}x < 3x or "
            f"kernel disagreement (agree={agree})",
            file=sys.stderr,
        )
        raise SystemExit(1)


# ------------------------------------------------- multi-tenant shared pool
def bench_multitenant_sweep(n: int) -> None:
    """Consolidated shared pool vs per-app dedicated deployments (ISSUE-8).

    The five paper apps at 1/8 rate — the low-rate regime where every
    plan strands a large fractional machine residue per module — are
    served two ways: per-app dedicated (every fractional allocation
    rounded up to whole devices: the integer bill a real deployment
    pays) and one shared pool (`SharedPool`: FFD co-location of residues
    under the calibrated interference model, co-located batches honestly
    slowed, e2e-SLO feasibility guard on every pairing).

    Acceptance (hard smoke gates): per-app frame accounting conserves;
    aggregate attainment >= 0.97 with interference ON; consolidated pool
    cost >= 1.15x cheaper than the dedicated bill.
    """
    import numpy as np

    from repro.serving import SharedPool
    from repro.serving.tenancy import dedicated_cost
    from repro.workloads.apps import app_by_name, make_workload

    seeds = (
        ("traffic", 100.0, 2.0), ("face", 150.0, 2.5), ("pose", 60.0, 3.0),
        ("caption", 90.0, 2.5), ("actdet", 80.0, 3.0),
    )
    scale = 0.125
    n_frames = 400 if SMOKE else max(800, min(n * 2, 2400))
    plans = {}
    for name, rate, slo in seeds:
        wl = make_workload(app_by_name(name), rate * scale, slo)
        plan = Planner(B.HARPAGON).plan(wl, PROFILES)
        if not plan.feasible:
            emit(
                f"multitenant_{name}", 0.0, "infeasible",
                app=name, feasible=False,
            )
            return
        plans[name] = plan
    pool = SharedPool(plans)
    t0 = time.perf_counter()
    res = pool.run(n_frames)
    dt = time.perf_counter() - t0
    conserved = all(res.conservation().values())
    for name, _, slo in seeds:
        r = res.results[name]
        att = float(
            (np.asarray(r.e2e_latencies) <= slo + 1e-9).sum()
            / max(1, r.offered)
        )
        emit(
            f"multitenant_{name}", 0.0,
            f"attain={att:.4f}|p99={r.p99:.3f}|offered={r.offered}",
            app=name, attainment=round(att, 4), p99=round(r.p99, 4),
            offered=r.offered, shed=r.shed, dropped=r.dropped,
        )
    emit(
        "multitenant_sweep",
        dt * 1e6,
        f"savings={res.savings:.3f}x|attain={res.attainment:.4f}"
        f"|pool={res.pool_cost:.4g}|dedicated={res.dedicated_cost:.4g}"
        f"|shared={res.device_plan.n_shared}/{len(res.device_plan.devices)}"
        f"|conserved={conserved}|target>=1.15x@0.97",
        savings=round(res.savings, 4),
        attainment=round(res.attainment, 4),
        pool_cost=round(res.pool_cost, 4),
        dedicated_cost=round(res.dedicated_cost, 4),
        n_devices=len(res.device_plan.devices),
        n_shared=res.device_plan.n_shared,
        conserved=bool(conserved),
    )
    if SMOKE and not conserved:
        print(
            "# SMOKE FAILURE: shared-pool frame accounting does not "
            f"conserve ({res.conservation()})",
            file=sys.stderr,
        )
        raise SystemExit(1)
    if SMOKE and (res.attainment < 0.97 or res.savings < 1.15):
        print(
            f"# SMOKE FAILURE: multitenant savings {res.savings:.3f}x < 1.15x "
            f"or attainment {res.attainment:.4f} < 0.97 (interference on)",
            file=sys.stderr,
        )
        raise SystemExit(1)


# ------------------------------------------------- failure resilience
def bench_chaos_sweep(n: int) -> None:
    """Failure-resilient serving under seeded fault injection (ISSUE-10).

    The 5-app diurnal preset is served with the full control stack
    (dummy streaming, burst-aware budget deadlines, epoch replans at
    ``margin=0.35``) three ways per app:

    * **baseline**: no fault injector — the no-fault attainment/cost;
    * **fault-off**: a *disabled* ``FaultConfig()`` — must be bit-exact
      with the baseline (the injector's plumbing is free when off);
    * **crash-per-epoch**: one seeded machine crash at every epoch
      midpoint (``detect_k=2`` watchdog), exercising silent-crash
      detection, frame-conserving re-queue, out-of-band failure replans,
      and warm-spare promotion end to end.

    Hard smoke gates: fault-off bit-exactness on every app; exact frame
    conservation (``completed + shed + dropped == offered``) and a
    conserved forensics cascade under the crash schedule; aggregate
    post-recovery attainment >= 0.9 at <= 1.3x the no-fault serving
    cost.  A second block sweeps the MTBF x detection-timeout grid on
    one app (informational rows: attainment / kills / re-queues per
    cell — how detection latency trades against false urgency).
    """
    import numpy as np

    from repro.serving import (
        ControlLoopConfig, FaultConfig, classify_misses, serving_cost,
    )
    from repro.serving.arrivals import trace_arrivals
    from repro.workloads.apps import app_by_name, make_workload

    seeds = (
        ("traffic", 100.0, 2.0), ("face", 150.0, 2.5), ("pose", 60.0, 3.0),
        ("caption", 90.0, 2.5), ("actdet", 80.0, 3.0),
    )
    derate = 1.25
    div = 12  # epochs per diurnal period
    detect_k = 2.0
    n_frames = 2400 if SMOKE else max(2400, min(n * 4, 4800))
    atts, ratios = [], []
    exact_all = conserved_all = True
    t0 = time.perf_counter()
    for name, rate, slo in seeds:
        period = n_frames / rate
        arr = trace_arrivals(n_frames, rate, seed=0, period=period)
        fe = FrontendConfig(dummies=True, burst_deadline=True)
        wl = make_workload(app_by_name(name), rate, slo / derate)
        plan = Planner(B.HARPAGON).plan(wl, PROFILES)
        if not plan.feasible:
            emit(f"chaos_{name}", 0.0, "infeasible", app=name, feasible=False)
            continue
        interval = period / div
        horizon = float(arr[-1])
        ctrl = lambda: ControlLoopConfig(  # noqa: E731
            interval=interval, profiles=PROFILES, margin=0.35
        )
        kw = dict(
            arrivals=arr, frontend=fe, pipeline=True, timeout="budget",
        )
        base = ServingEngine(plan).run(n_frames, rate, control=ctrl(), **kw)
        off = ServingEngine(plan).run(
            n_frames, rate, control=ctrl(), faults=FaultConfig(), **kw
        )
        exact = bool(
            np.array_equal(base.pipeline.e2e, off.pipeline.e2e, equal_nan=True)
        )
        sched = tuple(
            (interval * (k + 0.5), "crash")
            for k in range(int(horizon / interval))
        )
        fr = ServingEngine(plan).run(
            n_frames, rate, control=ctrl(),
            faults=FaultConfig(schedule=sched, seed=3, detect_k=detect_k),
            **kw,
        )
        att = lambda r: float(  # noqa: E731
            (np.asarray(r.e2e_latencies) <= slo + 1e-9).sum()
            / max(1, r.offered)
        )
        pr = fr.pipeline
        conserved = (
            int(pr.completed.sum() + pr.shed.sum() + pr.dropped.sum())
            == fr.offered
        )
        rep = classify_misses(pr, slo, fr.epochs)
        c_base = serving_cost(base.epochs, horizon)
        c_fault = serving_cost(fr.epochs, horizon)
        ratio = c_fault / c_base
        a = att(fr)
        atts.append(a)
        ratios.append(ratio)
        exact_all &= exact
        conserved_all &= conserved and rep.conserved
        emit(
            f"chaos_{name}",
            0.0,
            f"attain={a:.4f}|base={att(base):.4f}|cost_ratio={ratio:.3f}"
            f"|crashes={fr.faults['injected']}|killed={fr.faults['killed']}"
            f"|requeued={fr.faults['requeued']}|conserved={conserved}"
            f"|forensics={rep.conserved}|off_bitexact={exact}",
            app=name,
            attainment=round(a, 4),
            base_attainment=round(att(base), 4),
            cost_ratio=round(ratio, 4),
            crashes=fr.faults["injected"],
            killed=fr.faults["killed"],
            requeued=fr.faults["requeued"],
            machine_failure=rep.counts.get("machine_failure", 0),
            recovery_transient=rep.counts.get("recovery_transient", 0),
            conserved=bool(conserved),
            forensics_conserved=bool(rep.conserved),
            off_bitexact=exact,
        )
    mean_att = finite_mean(atts)
    worst_ratio = max(ratios) if ratios else math.nan
    emit(
        "chaos_sweep",
        (time.perf_counter() - t0) * 1e6,
        f"attain={mean_att:.4f}|worst_cost_ratio={worst_ratio:.3f}"
        f"|off_bitexact={exact_all}|conserved={conserved_all}"
        f"|target>=0.9@<=1.3x",
        attainment=round(mean_att, 4),
        worst_cost_ratio=round(worst_ratio, 4),
        off_bitexact=bool(exact_all),
        conserved=bool(conserved_all),
    )
    if SMOKE and not exact_all:
        print(
            "# SMOKE FAILURE: disabled fault injector is not bit-exact "
            "with the fault-free engine",
            file=sys.stderr,
        )
        raise SystemExit(1)
    if SMOKE and not conserved_all:
        print(
            "# SMOKE FAILURE: frame conservation or forensics cascade "
            "violated under the crash-per-epoch schedule",
            file=sys.stderr,
        )
        raise SystemExit(1)
    if SMOKE and (mean_att < 0.9 or worst_ratio > 1.3):
        print(
            f"# SMOKE FAILURE: chaos attainment {mean_att:.4f} < 0.9 or "
            f"cost ratio {worst_ratio:.3f} > 1.3x no-fault",
            file=sys.stderr,
        )
        raise SystemExit(1)

    # --- MTBF x detection-timeout grid (informational, one app) -----------
    name, rate, slo = seeds[0]
    period = n_frames / rate
    arr = trace_arrivals(n_frames, rate, seed=0, period=period)
    wl = make_workload(app_by_name(name), rate, slo / derate)
    plan = Planner(B.HARPAGON).plan(wl, PROFILES)
    interval = period / div
    for mtbf_mult in (1.0, 2.0):
        for k in (2.0, 4.0):
            fc = FaultConfig(
                mtbf=interval * mtbf_mult, kinds=("crash", "straggler"),
                seed=7, detect_k=k,
            )
            r = ServingEngine(plan).run(
                n_frames, rate,
                arrivals=arr,
                frontend=FrontendConfig(dummies=True, burst_deadline=True),
                pipeline=True, timeout="budget",
                control=ControlLoopConfig(
                    interval=interval, profiles=PROFILES, margin=0.35
                ),
                faults=fc,
            )
            a = float(
                (np.asarray(r.e2e_latencies) <= slo + 1e-9).sum()
                / max(1, r.offered)
            )
            emit(
                f"chaos_grid_m{mtbf_mult:g}_k{k:g}",
                0.0,
                f"attain={a:.4f}|injected={r.faults['injected']}"
                f"|killed={r.faults['killed']}"
                f"|requeued={r.faults['requeued']}",
                app=name,
                mtbf_epochs=mtbf_mult,
                detect_k=k,
                attainment=round(a, 4),
                injected=r.faults["injected"],
                killed=r.faults["killed"],
                requeued=r.faults["requeued"],
            )


# ----------------------------------------------------------- runtime
def bench_runtime(n: int) -> None:
    """Planner runtime vs brute force (paper: 5 ms vs 35.9 s, >7000x)."""
    wls = workload_suite(40)
    h = Planner(B.HARPAGON)
    t_h, t_bf, cnt = 0.0, 0.0, 0
    for wl in wls:
        t0 = time.perf_counter()
        plan = h.plan(wl, PROFILES)
        t_h += time.perf_counter() - t0
        if not plan.feasible:
            continue
        t0 = time.perf_counter()
        optimal_cost(wl, PROFILES)
        t_bf += time.perf_counter() - t0
        cnt += 1
    us = t_h * 1e6 / len(wls)
    derived = (
        f"harpagon={1e3*t_h/len(wls):.2f}ms|bruteforce={1e3*t_bf/max(1,cnt):.1f}ms"
        f"|speedup={t_bf/max(1,cnt)/(t_h/len(wls)):.0f}x|paper=5ms vs 35.9s"
    )
    emit("runtime_planner", us, derived)


BENCHES = {
    "table2": bench_table2,
    "fig5": bench_fig5_cost,
    "fig5b": bench_fig5_optimal,
    "fig6": bench_fig6_ablations,
    "fig7": bench_fig7_dispatch,
    "fig7sim": bench_fig7_simulation,
    "fig8": bench_fig8_multiconfig,
    "slo_sweep": bench_slo_sweep,
    "shed_sweep": bench_shed_sweep,
    "pipeline_sweep": bench_pipeline_sweep,
    "diurnal_sweep": bench_diurnal_sweep,
    "multitenant_sweep": bench_multitenant_sweep,
    "chaos_sweep": bench_chaos_sweep,
    "pipeline_speed": bench_pipeline_speed,
    "wallclock_gap": bench_wallclock_gap,
    "planner_speed": bench_planner_speed,
    "dp_splitter": bench_dp_splitter,
    "replay": bench_replay_speed,
    "runtime": bench_runtime,
}

# serving-subsystem rows tracked across PRs by `--json` (BENCH_serving.json)
_SERVING_PREFIXES = (
    "replay_", "slo_sweep_", "shed_sweep_", "shed_causes_", "pipeline_sweep_",
    "diurnal_", "multitenant_", "chaos_", "pipeline_speed", "planner_speed",
    "dp_splitter_", "wallclock_gap_",
)

# --smoke: CI-sized inputs + hard regression gates (see bench_replay_speed)
SMOKE = False


def main() -> None:
    global SMOKE
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--n", type=int, default=1131)
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="CI smoke mode: shrink inputs and FAIL (exit 1) on hot-path "
        "regressions (replay speedup / kernel agreement)",
    )
    ap.add_argument(
        "--json",
        nargs="?",
        const="BENCH_serving.json",
        default=None,
        metavar="PATH",
        help="write serving-bench rows (replay/pipeline speedups, SLO sweep, "
        "shed-rate sweep, diurnal control-plane sweep, planner speed) as "
        "machine-readable JSON (default path: BENCH_serving.json)",
    )
    ap.add_argument(
        "--profile",
        type=int,
        nargs="?",
        const=25,
        default=0,
        metavar="N",
        help="run each selected bench under cProfile and print its top-N "
        "functions by cumulative time (default N=25) — e.g. "
        "`--only pipeline_speed --profile` profiles the pipeline loop",
    )
    args = ap.parse_args()
    SMOKE = args.smoke
    print("name,us_per_call,derived")
    for name, fn in BENCHES.items():
        if args.only and name not in args.only.split(","):
            continue
        if args.profile:
            import cProfile
            import pstats

            prof = cProfile.Profile()
            prof.enable()
            try:
                fn(args.n)
            finally:
                prof.disable()
                print(f"# --- cProfile top {args.profile}: {name} ---", file=sys.stderr)
                stats = pstats.Stats(prof, stream=sys.stderr)
                stats.strip_dirs().sort_stats("cumulative").print_stats(args.profile)
        else:
            fn(args.n)
    if args.json:
        rows = [
            r for r in common.RECORDS if r["name"].startswith(_SERVING_PREFIXES)
        ]
        if rows:
            # merge-by-name into the tracked file: partial `--only` runs
            # update their rows in place, the union stays name-sorted
            # (`common.write_bench_json`, schema v2)
            common.write_bench_json(args.json, rows)
            print(
                f"# merged {len(rows)} serving rows into {args.json} "
                f"(schema v{common.SCHEMA_VERSION})",
                file=sys.stderr,
            )
        else:
            # don't clobber a tracked trajectory file with an empty record
            print(
                f"# no serving benches ran (need one of: replay, slo_sweep, "
                f"shed_sweep); {args.json} left untouched",
                file=sys.stderr,
            )


if __name__ == "__main__":
    main()
