"""End-to-end serving driver: profile -> plan -> serve, all real.

Mirrors the paper's pipeline exactly, on CPU:
  1. OFFLINE PROFILING: measure jitted batched forwards of two reduced
     assigned architectures at each batch size (the paper's "profiling
     library", Sec. III-A).
  2. PLAN: Harpagon splits the session SLO and schedules machines over the
     measured profiles; baselines planned for comparison.
  3. SERVE: a batched request stream runs through the plan with REAL model
     executions; SLO attainment is reported.

    PYTHONPATH=src python examples/serve_multidnn.py [--requests 300]
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import Leaf, Planner, Workload, series
from repro.core.baselines import BASELINES
from repro.core.dag import AppDAG
from repro.core.profiles import Config, ModuleProfile
from repro.models import Model


def profile_model(name: str, batches=(1, 2, 4, 8, 16)) -> tuple[ModuleProfile, callable]:
    """Offline profiling pass: measure the real jitted forward per batch size."""
    cfg = get_config(name, smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.key(0))

    @jax.jit
    def fwd(p, t):
        return model.forward(p, t).logits

    rows = []
    for b in batches:
        toks = jnp.zeros((b, 16), jnp.int32)
        fwd(params, toks).block_until_ready()  # compile
        t0 = time.perf_counter()
        reps = 3
        for _ in range(reps):
            fwd(params, toks).block_until_ready()
        d = (time.perf_counter() - t0) / reps
        rows.append(Config(b, round(d, 6), "cpu", 1.0))
    profile = ModuleProfile(name, tuple(rows))

    def executor(b):
        fwd(params, jnp.zeros((b, 16), jnp.int32)).block_until_ready()

    return profile, executor


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=300)
    ap.add_argument("--rate", type=float, default=40.0)
    ap.add_argument("--slo", type=float, default=2.0)
    ap.add_argument(
        "--sweep",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="after real serving, replay the plan in virtual time under "
        "uniform/poisson/bursty arrivals per planner preset",
    )
    args = ap.parse_args()

    archs = ["qwen2-vl-2b", "smollm-360m"]
    print("offline profiling (real jitted forwards)...")
    profiles, executors = {}, {}
    for a in archs:
        profiles[a], executors[a] = profile_model(a)
        rows = ", ".join(f"b{c.batch}:{c.duration*1e3:.1f}ms" for c in
                         sorted(profiles[a].configs, key=lambda c: c.batch))
        print(f"  {a}: {rows}")

    dag = AppDAG("vl-session", series(*[Leaf(a) for a in archs]))
    wl = Workload(dag, {a: args.rate for a in archs}, args.slo)
    plan = Planner().plan(wl, profiles)
    print("\n" + plan.summary())
    if not plan.feasible:
        raise SystemExit("infeasible — raise --slo or lower --rate")
    for opts in BASELINES:
        bl = Planner(opts).plan(wl, profiles)
        tag = f"{bl.cost:.2f} ({bl.cost / plan.cost:.2f}x)" if bl.feasible else "infeasible"
        print(f"  baseline {opts.name:<10} cost: {tag}")

    from repro.serving import ServingEngine

    engine = ServingEngine(plan, executors=executors)
    res = engine.run(args.requests, args.rate)
    print(
        f"\nserved {len(res.e2e_latencies)} frames with REAL executions: "
        f"SLO attainment {100 * res.attainment:.1f}%  p99 {res.p99:.3f}s (slo {args.slo}s)"
    )
    for m, st in res.module_stats.items():
        print(f"  {m}: {st.batches} batches, max module latency {st.max_latency:.3f}s")

    if args.sweep:
        # virtual-time replay of the measured profiles under arrival-process
        # diversity: the planner provisions for the uniform worst case
        # (Theorem 1); Poisson and bursty MMPP streams show how much SLO
        # attainment that steady-state assumption buys — per planner preset
        print("\narrival-process sweep (virtual time, measured profiles):")
        presets = [("harpagon", plan)] + [
            (o.name, p)
            for o in BASELINES
            if (p := Planner(o).plan(wl, profiles)).feasible
        ]
        print(f"  {'preset':<10} {'arrivals':<8} {'attain':>7} {'p99(s)':>8}")
        for name, p in presets:
            eng = ServingEngine(p, policy=p.options.policy)
            for kind in ("uniform", "poisson", "bursty"):
                r = eng.run(2000, args.rate, arrivals=kind, seed=0)
                print(
                    f"  {name:<10} {kind:<8} {100 * r.attainment:6.1f}% {r.p99:8.3f}"
                )

        # shed-rate sweep: drive the Harpagon plan past its provisioned rate
        # with bursty MMPP arrivals; without admission control the backlog
        # (and p99) grows with the run, while token-bucket / queue-depth
        # shedding at ingress bounds p99 at an explicit, reported shed rate.
        # Shed frames count as SLO misses in `attainment`.
        from repro.serving.frontend import FrontendConfig, QueueDepth, TokenBucket

        print("\nshed-rate sweep (MMPP overload, dummy streaming on):")
        fes = [
            ("none", FrontendConfig(dummies=True)),
            ("token-bucket", FrontendConfig(dummies=True, admission=TokenBucket(burst=4))),
            ("queue-depth", FrontendConfig(dummies=True, admission=QueueDepth(depth=8))),
        ]
        print(f"  {'admission':<13} {'load':<6} {'attain':>7} {'shed':>6} {'p99(s)':>8}")
        eng = ServingEngine(plan)
        for adm_name, fe in fes:
            for load in (1.0, 1.5, 3.0):
                r = eng.run(
                    2000, args.rate, arrivals="mmpp", seed=0, timeout="budget",
                    frontend=fe, offered_rate=load * args.rate,
                )
                print(
                    f"  {adm_name:<13} {load:<6g} {100 * r.attainment:6.1f}% "
                    f"{100 * r.shed / max(1, r.offered):5.1f}% {r.p99:8.3f}"
                )

        # control-plane demo: one diurnal period served by the epoch-based
        # incremental control loop (windowed rate estimation -> warm-start
        # Planner.replan -> live hot-swap) vs one static plan provisioned
        # for the diurnal peak.  Serving cost for the loop is the
        # time-integral of the active plan's cost across epochs.
        from repro.serving import ControlLoopConfig, serving_cost
        from repro.serving.arrivals import trace_arrivals

        print("\ndiurnal control plane (pipelined co-simulation):")
        n = 4000
        period = n / args.rate
        diurnal = trace_arrivals(n, args.rate, seed=0, period=period)
        fe = FrontendConfig(dummies=True)
        loop = ServingEngine(plan).run(
            n, args.rate, arrivals=diurnal, frontend=fe,
            control=ControlLoopConfig(
                interval=period / 48, profiles=profiles, margin=0.25
            ),
        )
        cost_loop = serving_cost(loop.epochs, float(diurnal[-1]))
        wl_peak = Workload(dag, {a: 1.8 * args.rate for a in archs}, args.slo)
        plan_peak = Planner().plan(wl_peak, profiles)
        swaps = sum(1 for e in loop.epochs if e.swapped)
        print(
            f"  replanning : cost {cost_loop:7.2f}  attain {100 * loop.attainment:5.1f}%"
            f"  ({swaps} swaps over {len(loop.epochs)} epochs, "
            f"final plan v{loop.epochs[-1].version})"
        )
        if plan_peak.feasible:
            static = ServingEngine(plan_peak).run(
                n, 1.8 * args.rate, arrivals=diurnal, frontend=fe
            )
            print(
                f"  static peak: cost {plan_peak.cost:7.2f}  attain {100 * static.attainment:5.1f}%"
                f"  -> replanning {plan_peak.cost / cost_loop:.2f}x cheaper"
            )
        else:
            print("  static peak: infeasible at 1.8x the provisioned rate "
                  "(raise --slo to compare)")


if __name__ == "__main__":
    main()
