"""Serving launcher: plan with Harpagon, then serve batched requests.

Plans a (possibly multi-module) session over the analytic TPU profiles and
serves it through the pipelined DAG co-simulation.  With --real, every
batch is a jitted forward of the module at published widths on the
attached TPU (seeded random weights and token ids), planned on that chip's
profile only, and batch service times are the measured forwards; --real
--smoke runs the reduced configs instead, which is how CPU tests drive the
path.  Without --real, profiled durations drive the event simulation.

  python -m repro.launch.serve --arch smollm-360m,gemma3-1b --real \
      --seq 128                       # the chip path (`chip_smoke.py`)

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
      --rate 200 --slo 0.5 --requests 2000
  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b,qwen1.5-4b \
      --rate 120 --slo 1.0            # two-module chain
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --real \
      --epoch 2.0                     # co-sim against measured step
                                      # times + epoch audit/replan
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
      --epoch 2.0 --arrivals diurnal --trace trace.json
                                      # observability on: per-epoch metrics,
                                      # SLO-miss forensics, Perfetto trace
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
      --epoch 2.0 --chaos             # one seeded machine crash per epoch:
                                      # watchdog detection, re-queue recovery,
                                      # failure replan + warm-spare promotion
"""
from __future__ import annotations

import argparse
import re
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..configs import ArchConfig, get_config
from ..core import Leaf, Workload, series
from ..core.baselines import ALL_SYSTEMS
from ..core.dag import AppDAG
from ..core.harpagon import Plan, Planner
from ..models import Model
from ..profiling import CATALOG, arch_profile, spec_for
from ..serving import (
    ControlLoopConfig,
    FaultConfig,
    LiveServiceTime,
    ServingEngine,
    SharedPool,
)
from ..serving.arrivals import trace_arrivals
from ..serving.observability import span
from .compile_cache import enable_compile_cache


def _named_forward(model: Model, name: str):
    """The module's forward as a function named for it, its body under
    ``jax.named_scope(name)``: the compiled executable (the device trace's
    XLA Modules line) and its ops' metadata then say which module ran.

    It returns the logits, and for an arch with MoE layers the pair
    ``(logits, routes)``: the expert ids each MoE layer chose for each token,
    ``(n_moe_layers, batch * seq, top_k)`` int32."""

    def forward(params, tokens):
        with jax.named_scope(name):
            out = model.forward(params, tokens, hold_experts=True)
        return out.logits if out.routes is None else (out.logits, out.routes)

    forward.__name__ = forward.__qualname__ = (
        "forward_" + re.sub(r"\W", "_", name)
    )
    return forward


class ModuleExecutor:
    """One module's jitted forward over seeded random token ids.

    ``ex(b)`` runs a ``(b, seq)`` batch on the default device and blocks
    until its output is ready (the `ServingEngine` executor contract), and
    returns it: the logits, or ``(logits, routes)`` for an MoE arch.  The
    first call at a batch size compiles it ahead of the run and records the
    compile seconds in ``compile_s`` (set-up, never a step).  Each later
    call is two spans (`serving.observability.spans`):
    ``dispatch <name> b<b>`` while the host enqueues the compiled forward,
    and ``sync <name> b<b>`` while it waits for the output.  ``name`` is
    ``cfg.name``: a module served by this executor must carry the arch's
    name, since its spans land on that module's registry row
    (`LiveServiceTime` refuses an executor bound to another name).
    """

    def __init__(self, cfg: ArchConfig, *, seq: int):
        self.cfg = cfg
        self.seq = seq
        self.name = cfg.name
        model = Model(cfg)
        self.params = jax.jit(model.init)(jax.random.key(0))
        self._fwd = jax.jit(_named_forward(model, self.name))
        self._tok_key = jax.random.key(1)
        self.compiled: dict[int, object] = {}
        self.compile_s: dict[int, float] = {}
        self._tokens: dict[int, jax.Array] = {}

    def __call__(self, b: int):
        if b not in self.compiled:
            toks = jax.random.randint(
                jax.random.fold_in(self._tok_key, b),
                (b, self.seq), 0, self.cfg.vocab_size, jnp.int32,
            )
            t0 = time.perf_counter()
            self.compiled[b] = self._fwd.lower(self.params, toks).compile()
            self.compile_s[b] = time.perf_counter() - t0
            self._tokens[b] = toks
        with span("dispatch", self.name, b):
            out = self.compiled[b](self.params, self._tokens[b])
        with span("sync", self.name, b):
            return jax.block_until_ready(out)


def build_executors(
    archs, *, seq: int, smoke: bool = False
) -> dict[str, ModuleExecutor]:
    """Executors for a chain of archs (published widths unless ``smoke``)."""
    return {a: ModuleExecutor(get_config(a, smoke=smoke), seq=seq) for a in archs}


def plan_batches(plan: Plan) -> dict[str, tuple[int, ...]]:
    """The batch sizes each module's allocations run at."""
    return {
        m: tuple(sorted({a.config.batch for a in s.allocs}))
        for m, s in plan.schedules.items()
    }


@dataclass
class ServeRun:
    """What one engine run of `main` produced (None for --pool/--compare)."""

    plan: Plan
    result: object  # ServeResult
    executors: dict[str, ModuleExecutor]
    live: "LiveServiceTime | None"


def _make_faults(args) -> "FaultConfig | None":
    """Resolve --chaos into a `FaultConfig` (None when the flag is absent).

    ``--chaos MTBF`` arms the seeded exponential crash process; a bare
    ``--chaos`` derives a deterministic schedule instead — one crash per
    epoch midpoint under ``--epoch``, a single mid-run crash otherwise.
    """
    if args.chaos is None:
        return None
    if args.chaos > 0.0:
        return FaultConfig(mtbf=args.chaos)
    horizon = args.requests / args.rate
    if args.epoch:
        sched = tuple(
            (args.epoch * (k + 0.5), "crash")
            for k in range(int(horizon / args.epoch))
        )
    else:
        sched = ((horizon / 2.0, "crash"),)
    return FaultConfig(schedule=sched)


def _serve_pool(args, archs, profiles) -> None:
    """--pool: each arch is its own single-module tenant; one shared pool."""
    plans = {}
    for a in archs:
        wl = Workload(AppDAG(a, series(Leaf(a))), {a: args.rate}, args.slo)
        plan = Planner().plan(wl, {a: profiles[a]})
        print(plan.summary())
        if not plan.feasible:
            raise SystemExit(f"infeasible workload for tenant {a}")
        plans[a] = plan
    pool = SharedPool(plans)
    print(pool.device_plan.summary())
    control = (
        ControlLoopConfig(interval=args.epoch, profiles=profiles)
        if args.epoch
        else None
    )
    if args.arrivals == "diurnal":
        arrivals = "uniform"  # per-tenant diurnal traces need per-app seeds
        print("(--pool serves diurnal tenants via --epoch control; "
              "arrival curve fixed to uniform per tenant)")
    else:
        arrivals = args.arrivals
    res = pool.run(
        args.requests,
        args.rate,
        arrivals=arrivals,
        control=control,
        observability=args.trace is not None,
        faults=_make_faults(args),
    )
    print(res.summary())
    print(
        f"consolidated {len(plans)} tenants onto "
        f"{len(res.device_plan.devices)} devices "
        f"({res.device_plan.n_shared} shared): pool cost {res.pool_cost:.4g} "
        f"vs dedicated {res.dedicated_cost:.4g} — {res.savings:.3f}x cheaper"
    )
    if args.trace is not None and res.trace is not None:
        path = res.trace.export(args.trace)
        print(f"wrote {len(res.trace.events())} pool trace events to {path}")


def main(argv=None) -> "ServeRun | None":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, help="comma-separated chain of archs")
    ap.add_argument("--rate", type=float, default=100.0)
    ap.add_argument("--slo", type=float, default=1.0)
    ap.add_argument("--requests", type=int, default=2000)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument(
        "--real", action="store_true",
        help="execute every batch as a jitted forward at published widths on "
        "the attached TPU, planned on that chip's profile (fails elsewhere "
        "unless --smoke)",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="reduced configs (with --real: runs on CPU, for tests)",
    )
    ap.add_argument("--compare", action="store_true", help="plan with all 5 systems")
    ap.add_argument(
        "--epoch", type=float, default=0.0,
        help="control-loop epoch interval in seconds (0 = control off); "
        "with --real each epoch audits modeled vs measured service time "
        "and replans against the corrected profiles",
    )
    ap.add_argument(
        "--arrivals", default="uniform",
        choices=["uniform", "poisson", "mmpp", "diurnal"],
        help="arrival process (diurnal = sinusoidal day/night trace whose "
        "period spans the run — the control plane's natural stressor)",
    )
    ap.add_argument(
        "--pool", action="store_true",
        help="serve each arch as an independent tenant on ONE shared device "
        "pool (multi-tenant: fractional machine residues co-located under "
        "the calibrated interference model, cost compared against dedicated "
        "per-tenant devices) instead of chaining the archs in series",
    )
    ap.add_argument(
        "--chaos", type=float, nargs="?", const=0.0, default=None,
        metavar="MTBF",
        help="seeded fault injection: machine crashes "
        "with the given mean-time-between-failures in seconds (omit the "
        "value for one crash per epoch with --epoch, or one mid-run crash "
        "without it) — exercises watchdog detection, frame-conserving "
        "re-queue, failure replans, and warm-spare promotion",
    )
    ap.add_argument(
        "--trace", nargs="?", const="trace.json", default=None, metavar="PATH",
        help="enable the observability layer: print the per-epoch metrics "
        "table and the SLO-miss forensics report, and export a Chrome/"
        "Perfetto trace-event JSON to PATH (default trace.json) — load it "
        "at https://ui.perfetto.dev",
    )
    args = ap.parse_args(argv)

    archs = args.arch.split(",")
    hardware = tuple(CATALOG)
    if args.real:
        if jax.default_backend() == "tpu":
            dev = jax.devices()[0]
            hardware = (spec_for(dev).name,)
            print(f"device: {dev.platform} {dev.device_kind!r} "
                  f"x{len(jax.devices())} -> {hardware[0]}")
        elif not args.smoke:
            raise SystemExit(
                f"--real serves published widths on a TPU; the backend is "
                f"{jax.default_backend()!r} (use --smoke on CPU)"
            )
    profiles = {
        a: arch_profile(
            get_config(a, smoke=args.smoke), seq=args.seq, hardware=hardware
        )
        for a in archs
    }

    if args.pool:
        if args.compare:
            ap.error("--pool and --compare are mutually exclusive")
        _serve_pool(args, archs, profiles)
        return None

    dag = AppDAG("session", series(*[Leaf(a) for a in archs]))
    wl = Workload(dag, {a: args.rate for a in archs}, args.slo)

    if args.compare:
        for opts in ALL_SYSTEMS:
            plan = Planner(opts).plan(wl, profiles)
            print(plan.summary())
        return None

    plan = Planner().plan(wl, profiles)
    print(plan.summary())
    if not plan.feasible:
        raise SystemExit("infeasible workload")

    faults = _make_faults(args)

    executors: dict[str, ModuleExecutor] = {}
    live = None
    if args.real:
        executors = build_executors(archs, seq=args.seq, smoke=args.smoke)
        # set-up: compile and run once every batch size the plan uses
        for m, bs in plan_batches(plan).items():
            for b in bs:
                executors[m](b)
                print(f"setup: compiled {m} b{b} in "
                      f"{executors[m].compile_s[b]:.3f}s")
        live = LiveServiceTime(executors)

    engine = ServingEngine(plan, executors=executors)
    control = (
        ControlLoopConfig(interval=args.epoch, profiles=profiles)
        if args.epoch
        else None
    )
    if args.arrivals == "diurnal":
        # one full day/night cycle across the run: the rate swings around
        # the provisioned one, which is what gives the control plane (and
        # the miss forensics' epoch attribution) something to chase
        arrivals = trace_arrivals(
            args.requests, args.rate, seed=0, period=args.requests / args.rate
        )
    else:
        arrivals = args.arrivals
    res = engine.run(
        args.requests,
        args.rate,
        arrivals=arrivals,
        control=control,
        service_time=live,
        observability=args.trace is not None,
        faults=faults,
    )
    print(
        f"served {len(res.e2e_latencies)} requests: SLO attainment "
        f"{100 * res.attainment:.2f}%  p99={res.p99:.4f}s  slo={args.slo}s"
    )
    if res.faults is not None:
        print(
            f"  chaos: {res.faults['injected']} faults injected, "
            f"{res.faults['killed']} machines declared dead, "
            f"{res.faults['requeued']} frames re-queued to survivors"
        )
    for m, st in res.module_stats.items():
        print(f"  {m}: batches={st.batches} max_latency={st.max_latency:.4f}s")
    if res.epochs:
        # the control loop's model-vs-measured audit: mean relative
        # |measured - modeled| service time per epoch, plus the profile
        # corrections the replan ran under
        for e in res.epochs:
            corr = (
                " corrections=" + ",".join(
                    f"{m}:{s:.2f}" for m, s in sorted(e.corrections.items())
                )
                if e.corrections
                else ""
            )
            print(
                f"  epoch t={e.t:8.3f}s target={e.target:8.1f}/s "
                f"cost={e.cost:7.1f} duration_err={e.duration_err:.3f}{corr}"
            )
    if args.trace is not None:
        if res.metrics is not None and res.metrics.rows:
            print(res.metrics.table())
        print(res.miss_report().table())
        if res.trace is not None:
            path = res.trace.export(args.trace)
            n_ev = len(res.trace.events())
            print(
                f"wrote {n_ev} trace events to {path} "
                f"(load at https://ui.perfetto.dev)"
            )
    return ServeRun(plan, res, executors, live)


if __name__ == "__main__":
    main()
