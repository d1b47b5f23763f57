"""Serving engine: executes a Harpagon Plan over a request stream.

`ServingEngine.run` serves a plan through the pipelined co-simulation
(`repro.serving.pipeline`): frames traverse the app DAG (Kahn toposort,
`core.dag.topo_sort`) as tracked entities inside one global event loop,
downstream ingress is fed by upstream batch completions, bounded queues
exert backpressure, and closed-loop clients plus admission run inside the
loop.  Per-module *fanout* (a detector emits several crops per frame; a
decoder consumes every other frame) gives module m ``rates[m] /
frame_rate`` instances per frame, exactly the rates the plan provisioned
for.  The returned ``ServeResult`` carries the full per-frame record in
``.pipeline``, including the per-module budget-overrun attribution that
checks `core.splitter` end to end.

Tail-batch semantics are real: with ``timeout`` set (seconds, or ``"budget"``
to derive a per-module collection deadline from the plan), partial batches
flush when their opener has waited that long — mid-stream under bursty
arrivals and at end of stream.  The default (``timeout=None, tail="flush"``)
reproduces the seed engine's numbers on uniform arrivals exactly (see
`repro.serving.reference`).

The optional *frontend* (`repro.serving.frontend`) sits between arrivals and
dispatch: it streams the plan's priced dummy traffic as phantom requests
(excluded from all statistics, counted in batch fill), sheds frames at
ingress under an admission policy, and can replace the open-loop arrival
process with closed-loop clients.  ``run(..., offered_rate=...)`` drives the
plan past its provisioned rate while keeping the provisioned fanout — the
honest overload experiment the frontend exists for.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Mapping, Sequence

import numpy as np

from ..core.dag import Workload, topo_sort
from ..core.dispatch import Machine, Policy, expand_machines, remaining_workloads
from ..core.harpagon import Plan
from .arrivals import make_arrivals
from .control import ControlLoopConfig, ControlRuntime, plan_e2e_hint
from .faults import FaultConfig, FaultRuntime
from .frontend import FrontendConfig, make_admission
from .observability import Observability, active, annotate
from .pipeline import ModuleStage, PipelineConfig, make_stage_fanouts
from .pipeline.core import run_pipeline
from .service_time import (
    DegradedServiceTime,
    LiveServiceTime,
    ServiceTimeSource,
    resolve_service_time,
)


@dataclass
class ModuleStats:
    latencies: list[float] = field(default_factory=list)
    batches: int = 0
    dropped: int = 0
    phantom: int = 0  # frontend dummy requests streamed through this module

    @property
    def max_latency(self) -> float:
        return max(self.latencies) if self.latencies else 0.0


@dataclass
class ServeResult:
    e2e_latencies: list[float]
    module_stats: dict[str, ModuleStats]
    slo: float
    shed: int = 0      # frames rejected at ingress by the admission controller
    dropped: int = 0   # admitted frames lost mid-pipeline (tail drops etc.)
    attempts: int = 0  # closed-loop issue attempts incl. retries (0 = open loop)
    pipeline: "object | None" = None  # PipelineResult: the per-frame record
    epochs: "list | None" = None      # EpochRecords when run(control=...)
    metrics: "object | None" = None   # MetricsSnapshot when run(observability=...)
    trace: "object | None" = None     # TraceRecorder when tracing was enabled
    # fault-injection tally when run(faults=...): faults injected, machines
    # declared dead, unfinished members re-queued to surviving siblings
    faults: "dict[str, int] | None" = None

    @property
    def offered(self) -> int:
        """Total frames offered to the system: completed + shed + dropped."""
        return len(self.e2e_latencies) + self.shed + self.dropped

    @property
    def attainment(self) -> float:
        """SLO attainment over *offered* frames: a shed or dropped frame is a
        miss, not a statistical no-show (an all-shed run attains 0.0)."""
        total = self.offered
        if total == 0:
            return 1.0
        ok = sum(1 for l in self.e2e_latencies if l <= self.slo + 1e-9)
        return ok / total

    @property
    def p99(self) -> float:
        if not self.e2e_latencies:
            return 0.0
        return float(np.quantile(np.asarray(self.e2e_latencies), 0.99))

    def miss_report(self, slo: "float | None" = None):
        """SLO-miss forensics (`observability.forensics.MissReport`): every
        missed or shed frame classified into exactly one cause, conservation
        checked against ``offered - completed-in-SLO``.  The control plane's
        epoch audit trail (when one ran) refines the classification."""
        return self.pipeline.miss_report(
            self.slo if slo is None else slo, self.epochs
        )


def plan_burst(plan: Plan, m: str) -> float:
    """One upstream batch-arrival quantum for module ``m`` under ``plan``.

    Arrivals at a module downstream of a batched stage come quantized in
    its parents' batch completions: up to ``max(b_up) / rate_up`` seconds
    of arrivals land at once, and the *gap* between completions is as long.
    The same quantity `Planner._burst_of` uses on the WCL side
    (``PlannerOptions(burst_aware=True)``), exposed here for the deadline
    side (`resolve_module_timeout(..., burst=...)`).  Zero for sources.
    """
    wl = plan.workload
    burst = 0.0
    for p in wl.app.parents(m):
        s = plan.schedules.get(p)
        if s is None or not s.allocs:
            continue
        b_up = max(a.config.batch for a in s.allocs)
        burst = max(burst, b_up / max(s.rate, 1e-12))
    return burst


# padded-fill floor factor for burst-aware budget deadlines: the adaptive
# phantom injector's pacing law delivers ~C/1.5 in a deep lull (one 1.5-slot
# grace per injection, deficit forgiven at each anchor resync), and its
# backlog-yield suppresses it further while queued batches drain — 2x the
# nominal fill time covers both, validated against the diurnal sweep's lull
# phase (see `benchmarks.run --only diurnal_sweep`)
_PAD_FILL = 2.0


def resolve_module_timeout(
    schedule,
    machines: "list[Machine]",
    timeout: "float | str | None",
    policy: Policy,
    *,
    dummies: bool = False,
    burst: "float | None" = None,
    rate_scale: float = 1.0,
) -> "float | None | dict[int, float]":
    """Resolve the batch-collection deadline for one module schedule.

    ``"budget"`` derives a per-machine deadline from the plan: each machine
    must flush early enough that collection + its own service duration still
    fits the module's latency budget.  A module-level function so the
    control plane (`repro.serving.control`) can resolve deadlines for
    hot-swapped schedules exactly like the engine resolves the initial ones.

    ``burst`` (pass ``burst=None`` for the flag-off path) is the burst-aware
    *deadline* correction — the PR-4 finding's fix, mirroring the
    burst-aware WCL quantum (`repro.core.dispatch.config_wcl`) on the
    deadline side, opt-in via ``FrontendConfig(burst_deadline=True)``.  Two
    corrections compose on the dummy-streaming path:

    * **one upstream batch-arrival quantum** (`plan_burst`, seconds):
      downstream of a batched stage the inter-completion gap can straddle a
      zero-slack ``budget - d`` deadline, flushing a partial batch whose
      wasted service snowballs at 100% utilization (attainment below 0.5 at
      1.0x provisioning on uniform arrivals).  Adding the quantum lets the
      batch survive the gap and fill from the next completion;
    * **the padded-fill floor**: the adaptive injector is rate-limited with
      a 1.5-slot pacing law (anchor resync forgives old deficit), so its
      achievable collection in a lull is ~``2/3`` of the provisioned rate
      ``C``, and it yields entirely while real service backlog exists — a
      deadline at the nominal ``b / C`` fill time then flushes a
      nearly-empty batch on *every* cycle once traffic runs below
      provisioning (the diurnal-lull collapse).  The floor
      ``_PAD_FILL * (b + 1.5) / C`` is the fill time under that pacing law
      plus arming lag, so a flush only ever fires on a batch the injector
      could not have filled.

    Both trade modeled-WCL tightness (a deadline may exceed ``budget - d``
    by the quantum + floor slack) for flush stability — the same contract
    as ``PlannerOptions(burst_aware=True)`` on the WCL side.  Flag off
    (``burst=None``) keeps the exact PR-4 semantics, collapse included.

    ``rate_scale`` (< 1.0) is the control plane's transient-aware deadline
    relaxation (`ControlRuntime.on_tick`): when arrivals run below the
    plan's provisioned rate mid-epoch, the burst-corrected deadlines are
    re-resolved as if the collect rate were ``scale * C`` — the padded-fill
    floor and the burst quantum both stretch by ``1 / scale`` toward the
    *observed* arrival quantum, so a stale plan stops flushing near-empty
    batches.  The default 1.0 is an exact no-op, and only the
    dummy-streaming burst-aware branch consumes it.
    """
    if timeout is None or isinstance(timeout, (int, float)):
        return timeout
    if timeout == "budget":
        s = schedule
        if dummies:
            # the frontend streams the plan's dummy traffic, so batches
            # collect at the provisioned rate and the deadline can sit
            # exactly at the modeled budget (+ the opt-in burst corrections)
            if burst is None:
                return {
                    mm.mid: max(s.budget - mm.config.duration, 0.0)
                    for mm in machines
                }
            coll = sum(a.rate + a.dummy for a in s.allocs) * rate_scale
            return {
                mm.mid: max(
                    s.budget - mm.config.duration,
                    _PAD_FILL * (mm.config.batch + 1.5) / max(coll, 1e-12),
                ) + burst / rate_scale
                for mm in machines
            }
        # floor at the real-rate fill time: dummy-padded plans assume the
        # frontend injects phantom requests to speed collection, which the
        # engine does not simulate — flushing faster than real traffic can
        # fill a batch would silently overload the machine instead.  Under
        # TC machine i's batch is a consecutive slice of the stream, but
        # it fills at the *remaining* workload w_i (Theorem 1): a
        # lower-ranked machine sees only the traffic dispatched at or
        # below its rank, so its honest floor is longer than the whole-
        # module fill time.  Under RR/DT a machine fills only at its own
        # share of the traffic.
        if policy is Policy.TC:
            w_of = remaining_workloads(list(s.allocs))
            def fill(mm: Machine) -> float:
                return mm.config.batch / max(w_of.get(mm.mid, s.rate), 1e-12)
        else:
            tot = sum(mm.rate for mm in machines)
            def fill(mm: Machine) -> float:
                rate = s.rate
                if tot > 0:
                    rate *= mm.rate / tot
                return mm.config.batch / max(rate, 1e-12)
        return {
            mm.mid: max(s.budget - mm.config.duration, fill(mm))
            for mm in machines
        }
    raise ValueError(f"unknown timeout spec {timeout!r}")


class ServingEngine:
    def __init__(
        self,
        plan: Plan,
        *,
        executors: Mapping[str, Callable[[int], None]] | None = None,
        policy: Policy = Policy.TC,
    ):
        """``executors[module](batch_size)`` runs a real batched forward; when
        None the profiled config duration is used (virtual time)."""
        self.plan = plan
        self.executors = executors or {}
        self.policy = policy

    def run(
        self,
        n_frames: int,
        frame_rate: float,
        *,
        arrivals: "str | np.ndarray | Sequence[float]" = "uniform",
        seed: int = 0,
        timeout: "float | str | None" = None,
        tail: str = "flush",
        frontend: FrontendConfig | None = None,
        offered_rate: float | None = None,
        pipeline: "bool | PipelineConfig" = True,
        control: "ControlLoopConfig | None" = None,
        service_time: "str | ServiceTimeSource | None" = None,
        observability: "bool | object | None" = None,
        faults: "FaultConfig | None" = None,
    ) -> ServeResult:
        """Serve ``n_frames`` frames arriving at ``offered_rate`` (default:
        the provisioned ``frame_rate``) through the planned DAG.

        ``frame_rate`` stays the *provisioned* rate: it fixes the per-module
        fanout and the admission controller's default budget, so passing
        ``offered_rate > frame_rate`` drives the plan into overload without
        silently rescaling the workload shape.  ``frontend`` enables dummy
        streaming / admission control / closed-loop clients (`FrontendConfig`);
        with ``frontend.clients`` set the ``arrivals`` process is ignored —
        issue times come from the client loop.

        ``pipeline`` is ``True`` (the default `PipelineConfig`: unbounded
        queues, deterministic fanout) or a
        `repro.serving.pipeline.PipelineConfig` for bounded queues,
        stochastic fanout or the ``reference=True`` oracle loop.

        ``control`` (a `repro.serving.control.ControlLoopConfig`) runs the
        incremental control plane inside the event loop: windowed
        arrival-rate estimation, warm-start ``Planner.replan`` at every
        epoch, and hot-swap of the resulting plan delta onto the live
        stages.  The returned ``ServeResult.epochs`` carries the per-epoch
        audit trail.

        ``service_time`` selects where batch service durations come from
        (`repro.serving.service_time`): ``None`` / ``"analytic"`` is the
        profiled constant; a `TraceServiceTime` replays recorded
        per-(module, batch) samples deterministically; ``"live"`` (or a
        `LiveServiceTime`) times the engine's real executors per batch.
        Real executors with ``service_time=None`` auto-wrap into a live
        source, so the loop co-simulates against measured step times;
        combined with ``control=`` the epochs replan against observed
        durations (model-vs-measured error in each EpochRecord).

        ``observability`` (``True``, an `ObservabilityConfig`, or a prebuilt
        `Observability`) attaches the passive telemetry layer: a structured
        trace recorder (Perfetto-exportable) and a per-epoch metrics
        registry, returned as ``ServeResult.trace`` / ``.metrics``.  The
        sink is write-only — results are bit-identical with it on, off, or
        sampled.  Off (``None``, the default) costs nothing.

        ``faults`` (a `repro.serving.faults.FaultConfig`) arms the seeded
        fault injector: machine crashes, transient stragglers, and
        whole-device losses fire as events inside the co-simulation, a
        batch-duration watchdog escalates unresponsive machines suspect →
        dead, dead machines' unfinished work re-queues to surviving
        siblings, and the control plane (when one runs) force-replans the
        failed module out-of-band.  A disabled config (neither ``mtbf`` nor
        ``schedule`` set) is treated exactly like ``faults=None`` —
        bit-exact with the injector absent.

        The run is a ``serve`` annotation on the profiler's clock (see
        `observability.spans`); while ``observability`` is on, its runtime
        is the one the served path's spans feed, and each garbage
        collection is spanned, until the run returns.
        """
        if pipeline is True:
            cfg = PipelineConfig()
        elif isinstance(pipeline, PipelineConfig):
            cfg = pipeline
        else:
            raise TypeError(
                f"pipeline= expects True or PipelineConfig, got {pipeline!r}"
            )
        fe = frontend or FrontendConfig()
        obs = Observability.make(observability)
        wl: Workload = self.plan.workload
        ctrl = make_admission(fe.admission, wl.app.name, frame_rate)
        if offered_rate is not None and offered_rate <= 0:
            raise ValueError("offered_rate must be positive")
        if faults is not None:
            if not isinstance(faults, FaultConfig):
                raise TypeError(f"faults= expects FaultConfig, got {faults!r}")
            if not faults.enabled:
                faults = None  # nothing to fire: identical to faults=None
        with annotate("serve"), active(obs):
            service_time = resolve_service_time(service_time, self.executors)
            if service_time is None and self.executors:
                # real executors: co-simulate against measured step times
                # (timed per batch, steady-state cached per config)
                service_time = LiveServiceTime(self.executors)
            rt_faults = None
            if faults is not None:
                rt_faults = FaultRuntime(faults)
                # straggler faults inflate durations live through the
                # service-time hook: the wrapper holds the injector's slowdown
                # table by reference, so entering/leaving it needs no stage state
                service_time = DegradedServiceTime(rt_faults.slow, service_time)
            topo = topo_sort(wl.app.modules, wl.app.edges)
            sources = [m for m in topo if not wl.app.parents(m)]
            fanouts = {m: wl.rates[m] / frame_rate for m in topo}
            stage_fanouts = make_stage_fanouts(
                cfg.fanout, fanouts, sources, n_frames, seed=seed + 1
            )
            stages = {}
            for m in topo:
                s = self.plan.schedules[m]
                machines = expand_machines(list(s.allocs))
                w = self._module_timeout(
                    m, machines, timeout,
                    dummies=fe.dummies, burst_deadline=fe.burst_deadline,
                )
                # adaptive dummy streaming: pad the stage's collection up to
                # the provisioned collect rate (real + priced dummy) —
                # phantoms flow exactly when real traffic lags the rate the
                # budget deadline assumes
                target = (
                    sum(a.rate + a.dummy for a in s.allocs) if fe.dummies else 0.0
                )
                stages[m] = ModuleStage(
                    m,
                    machines,
                    self.policy,
                    timeout=w,
                    fanout=stage_fanouts[m],
                    phantom_target=target,
                    queue_cap=cfg.queue_cap,
                    service_time=service_time,
                )
            rt = None
            if control is not None:
                if not isinstance(control, ControlLoopConfig):
                    raise TypeError(
                        f"control= expects ControlLoopConfig, got {control!r}"
                    )
                if control.profiles is None:
                    raise ValueError(
                        "control.profiles must carry the module profiles so "
                        "Planner.replan can re-solve modules at epoch boundaries"
                    )
                rt = ControlRuntime(
                    control,
                    self.plan,
                    control.profiles,
                    frame_rate,
                    timeout_of=lambda s_, machines_, plan_, rate_scale=1.0: (
                        resolve_module_timeout(
                            s_, machines_, timeout, self.policy,
                            dummies=fe.dummies,
                            burst=(
                                plan_burst(plan_, s_.module)
                                if (fe.burst_deadline and fe.dummies)
                                else None
                            ),
                            rate_scale=rate_scale,
                        )
                    ),
                    dummies=fe.dummies,
                    admission=ctrl,
                    # deadline relaxation applies to provisioned-collect-rate
                    # deadlines only: the dummy-padded "budget" path with the
                    # burst-aware corrections is exactly that regime
                    relax=(
                        fe.dummies and fe.burst_deadline and timeout == "budget"
                    ),
                )
                if service_time is not None:
                    # feed every started batch's measured duration to the
                    # control plane: epochs replan against corrected
                    # profiles and record the model-vs-measured error
                    for st in stages.values():
                        st.service_obs = rt.observe_service
            pace = offered_rate if offered_rate is not None else frame_rate
            if ctrl is not None:
                ctrl.reset()
                # admission emits its own decision-resolution telemetry:
                # every denial, with interim retry denials the closed loop
                # later re-admits tagged "shed_retry" (terminal ones
                # "shed"); the loop's terminal emit defers to it (see
                # `pipeline.core.issue_frame`) so sheds are never
                # double-counted
                ctrl.obs = obs
            if fe.clients is not None:
                ingress = dict(clients=fe.clients, pace=pace)
            else:
                ingress = dict(issue=make_arrivals(arrivals, n_frames, pace, seed=seed))
            res = run_pipeline(
                wl.app, stages, n_frames,
                admission=ctrl, tail=tail, seed=seed, control=rt,
                e2e_hint=plan_e2e_hint(self.plan), obs=obs, faults=rt_faults,
                reference=cfg.reference, fast_path=cfg.fast_path, **ingress,
            )
            stats = {}
            for m in topo:
                ss = res.stats[m]
                stats[m] = ModuleStats(
                    latencies=ss.latencies,
                    batches=ss.batches,
                    dropped=ss.dropped,
                    phantom=ss.phantom,
                )
            out = ServeResult(
                res.e2e[res.completed].tolist(),
                stats,
                wl.slo,
                shed=int(res.shed.sum()),
                dropped=int(res.dropped.sum()),
                attempts=res.attempts,
                pipeline=res,
                epochs=rt.history if rt is not None else None,
                faults=(
                    {
                        "injected": rt_faults.n_injected,
                        "killed": rt_faults.n_killed,
                        "requeued": rt_faults.n_requeued,
                    }
                    if rt_faults is not None
                    else None
                ),
            )
            if obs is not None:
                t_end = 0.0
                for m in topo:
                    col = res.finish[m]
                    v = col[~np.isnan(col)]
                    if v.size:
                        t_end = max(t_end, float(v.max()))
                out.metrics = obs.finalize(
                    t_end, {m: len(stages[m].machines) for m in topo}
                )
                out.trace = obs.trace
            return out

    def _module_timeout(
        self,
        m: str,
        machines: "list[Machine]",
        timeout: "float | str | None",
        *,
        dummies: bool = False,
        burst_deadline: bool = False,
    ) -> "float | None | dict[int, float]":
        burst = plan_burst(self.plan, m) if (burst_deadline and dummies) else None
        return resolve_module_timeout(
            self.plan.schedules[m], machines, timeout, self.policy,
            dummies=dummies, burst=burst,
        )
