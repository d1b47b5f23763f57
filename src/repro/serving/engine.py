"""Serving engine: executes a Harpagon Plan over a request stream.

Thin adapter over the unified simulation subsystem: arrival processes come
from `repro.serving.arrivals` (uniform / poisson / bursty MMPP / diurnal
trace), per-module batch replay runs on the numpy-vectorized kernel
(`repro.serving.replay`) in virtual time, and on the discrete-event core
(`repro.serving.events`) when real jitted executors are attached (wall-clock
measured, used by the end-to-end example).

Requests flow through the app DAG (Kahn toposort, `core.dag.topo_sort`) with
per-module *fanout* (a detector emits several crops per frame; a decoder
consumes every other frame): module m sees ``rates[m] / frame_rate``
instances per frame, exactly the rates the plan provisioned for.

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

``run(..., pipeline=True)`` switches from the per-module topological replay
to the multi-module pipelined co-simulation (`repro.serving.pipeline`):
frames traverse the DAG as tracked entities, downstream ingress is fed by
upstream batch completions, bounded queues exert backpressure, fanout can be
per-frame stochastic (correlated across siblings), and closed-loop clients
plus admission run *inside* the event loop.  The returned ``ServeResult``
then carries the full per-frame record in ``.pipeline`` — including the
per-module budget-overrun attribution that gives `core.splitter` its first
honest end-to-end check.  The default (``pipeline=False``) is the flat path,
bit-identical to before.
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass, field
from typing import Callable, Mapping, Sequence

import numpy as np

from ..core.dag import Workload, topo_sort
from ..core.dispatch import (
    Machine,
    Policy,
    dispatch_runs,
    expand_machines,
    remaining_workloads,
)
from ..core.harpagon import Plan
from .arrivals import make_arrivals
from .events import simulate_module_events
from .faults import FaultConfig, FaultRuntime
from .frontend import FrontendConfig, make_admission
from .frontend.clients import closed_loop_ingress
from .frontend.dummy import merge_phantoms, phantom_times
from .observability import Observability, active, annotate
from .replay import (
    ModuleReplay,
    causal_order,
    expand_fanout,
    lexmax_fold,
    lexmax_parents,
    propagate_depth,
    replay_module,
    runs_to_assignment,
)
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
    pipeline: "object | None" = None  # PipelineResult when run(pipeline=...)
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
        checked against ``offered - completed-in-SLO``.  Needs the per-frame
        record, so pipeline-mode runs only; the control plane's epoch audit
        trail (when one ran) refines the classification."""
        if self.pipeline is None:
            raise ValueError(
                "miss_report needs the per-frame record: run(pipeline=True)"
            )
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
        pipeline: "bool | object" = False,
        control: "object | None" = None,
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

        ``pipeline`` selects the multi-module co-simulation (``True`` or a
        `repro.serving.pipeline.PipelineConfig` for bounded queues and
        stochastic fanout); the default flat path replays modules in
        topological order with unbounded hand-off.

        ``control`` (a `repro.serving.control.ControlLoopConfig`, pipeline
        mode only) runs the incremental control plane inside the event loop:
        windowed arrival-rate estimation, warm-start ``Planner.replan`` at
        every epoch, and hot-swap of the resulting plan delta onto the live
        stages.  The returned ``ServeResult.epochs`` carries the per-epoch
        audit trail.  With ``control=None`` the path is bit-identical to
        before the control plane existed.

        ``service_time`` selects where batch service durations come from
        (`repro.serving.service_time`): ``None`` / ``"analytic"`` is the
        profiled constant (bit-exact default); a `TraceServiceTime` replays
        recorded per-(module, batch) samples deterministically; ``"live"``
        (or a `LiveServiceTime`) times the engine's real executors per
        batch.  In pipeline mode real executors auto-wrap into a live
        source, so ``run(pipeline=True)`` co-simulates against measured
        step times; combined with ``control=`` the epochs replan against
        observed durations (model-vs-measured error in each EpochRecord).

        ``observability`` (``True``, an `ObservabilityConfig`, or a prebuilt
        `Observability`) attaches the passive telemetry layer: a structured
        trace recorder (Perfetto-exportable) and a per-epoch metrics
        registry, returned as ``ServeResult.trace`` / ``.metrics``.  The
        sink is write-only — results are bit-identical with it on, off, or
        sampled.  Off (``None``, the default) costs nothing.

        ``faults`` (a `repro.serving.faults.FaultConfig`, pipeline mode
        only) arms the seeded fault injector: machine crashes, transient
        stragglers, and whole-device losses fire as events inside the
        co-simulation, a batch-duration watchdog escalates unresponsive
        machines suspect → dead, dead machines' unfinished work re-queues
        to surviving siblings, and the control plane (when one runs)
        force-replans the failed module out-of-band.  A disabled config
        (neither ``mtbf`` nor ``schedule`` set) is treated exactly like
        ``faults=None`` — bit-exact with the injector absent.

        The run is a ``serve`` annotation on the profiler's clock (see
        `observability.spans`); while ``observability`` is on, its runtime
        is the one the served path's spans feed, and each garbage
        collection is spanned, until the run returns.
        """
        fe = frontend or FrontendConfig()
        obs = Observability.make(observability)
        wl: Workload = self.plan.workload
        ctrl = make_admission(fe.admission, wl.app.name, frame_rate)
        if offered_rate is not None and offered_rate <= 0:
            raise ValueError("offered_rate must be positive")
        if control is not None and not pipeline:
            raise ValueError(
                "control= (epoch-based plan hot-swap) requires pipeline mode: "
                "the flat path replays whole modules and cannot swap mid-run"
            )
        if faults is not None:
            if not isinstance(faults, FaultConfig):
                raise TypeError(f"faults= expects FaultConfig, got {faults!r}")
            if not faults.enabled:
                faults = None  # nothing to fire: identical to faults=None
        if faults is not None and not pipeline:
            raise ValueError(
                "faults= (seeded fault injection) requires pipeline mode: "
                "the flat path replays whole modules and has no machines to "
                "fail mid-run"
            )
        with annotate("serve"), active(obs):
            src = resolve_service_time(service_time, self.executors)
            if pipeline:
                return self._run_pipeline(
                    n_frames, frame_rate, fe, ctrl,
                    arrivals=arrivals, seed=seed, timeout=timeout, tail=tail,
                    offered_rate=offered_rate, cfg=pipeline, control=control,
                    service_time=src, obs=obs, faults=faults,
                )
            if fe.clients is not None:
                warnings.warn(
                    "the fixed-point closed loop (clients= without pipeline=True) "
                    "is deprecated: the event-interleaved co-simulation "
                    "(pipeline=True) replaces the latency-oracle iteration",
                    DeprecationWarning,
                    stacklevel=2,
                )
                return self._run_closed_loop(
                    n_frames, frame_rate, fe, ctrl,
                    seed=seed, timeout=timeout, tail=tail,
                    offered_rate=offered_rate,
                )
            arrival = make_arrivals(
                arrivals, n_frames,
                offered_rate if offered_rate is not None else frame_rate,
                seed=seed,
            )
            if ctrl is not None:
                ctrl.reset()
                ctrl.obs = obs  # flat path: ingress sheds land in the telemetry
                shed_mask = ctrl.shed_stream(arrival)
            else:
                shed_mask = np.zeros(n_frames, dtype=bool)
            result, lat = self._serve(
                arrival, shed_mask, frame_rate, fe, timeout=timeout, tail=tail,
                service_time=src, obs=obs,
            )
            if obs is not None:
                fin = arrival + lat
                t_end = (
                    float(np.nanmax(fin))
                    if np.isfinite(fin).any()
                    else (float(arrival.max()) if arrival.size else 0.0)
                )
                machines_of = {
                    m: len(expand_machines(list(s.allocs)))
                    for m, s in self.plan.schedules.items()
                }
                result.metrics = obs.finalize(t_end, machines_of)
                result.trace = obs.trace
            return result

    def _run_closed_loop(
        self,
        n_frames: int,
        frame_rate: float,
        fe: FrontendConfig,
        ctrl,
        *,
        seed: int,
        timeout: "float | str | None",
        tail: str,
        offered_rate: float | None,
    ) -> ServeResult:
        """Fixed point of (client ingress -> DAG replay -> latency oracle).

        The ingress simulation needs each frame's end-to-end latency to know
        when its client slot frees; the DAG replay needs the arrival times.
        Successive substitution from the plan's modeled latency converges in
        a few iterations (under overload the closed loop self-throttles, so
        latencies barely move between rounds).
        """
        wl = self.plan.workload
        clients = fe.clients
        est0 = self.plan.e2e_latency
        if not np.isfinite(est0) or est0 <= 0.0:
            est0 = wl.slo
        est = np.full(n_frames, max(est0, 1e-6))
        pace = offered_rate if offered_rate is not None else frame_rate
        result = ServeResult([], {}, wl.slo)
        prev_arrival: np.ndarray | None = None
        for _ in range(max(1, clients.max_iters)):
            if ctrl is not None:
                ctrl.reset()
            arrival, shed_mask, attempts = closed_loop_ingress(
                clients, n_frames, pace, est, admission=ctrl, seed=seed
            )
            result, lat = self._serve(
                arrival, shed_mask, frame_rate, fe, timeout=timeout, tail=tail
            )
            result.attempts = attempts
            est = np.where(np.isfinite(lat), lat, est)
            if (
                prev_arrival is not None
                and float(np.max(np.abs(arrival - prev_arrival))) < clients.tol
            ):
                break
            prev_arrival = arrival
        return result

    def _run_pipeline(
        self,
        n_frames: int,
        frame_rate: float,
        fe: FrontendConfig,
        ctrl,
        *,
        arrivals: "str | np.ndarray | Sequence[float]",
        seed: int,
        timeout: "float | str | None",
        tail: str,
        offered_rate: float | None,
        cfg,
        control=None,
        service_time: "ServiceTimeSource | None" = None,
        obs: "Observability | None" = None,
        faults: "FaultConfig | None" = None,
    ) -> ServeResult:
        """Multi-module pipelined co-simulation (`repro.serving.pipeline`)."""
        from .control import ControlLoopConfig, ControlRuntime, plan_e2e_hint
        from .pipeline import ModuleStage, PipelineConfig, make_stage_fanouts
        from .pipeline.core import run_pipeline

        if cfg is True:
            cfg = PipelineConfig()
        if not isinstance(cfg, PipelineConfig):
            raise TypeError(f"pipeline= expects True or PipelineConfig, got {cfg!r}")
        if service_time is None and self.executors:
            # real executors in pipeline mode: co-simulate against measured
            # step times (timed per batch, steady-state cached per config)
            service_time = LiveServiceTime(self.executors)
        rt_faults = None
        if faults is not None:
            rt_faults = FaultRuntime(faults)
            # straggler faults inflate durations live through the
            # service-time hook: the wrapper holds the injector's slowdown
            # table by reference, so entering/leaving it needs no stage state
            service_time = DegradedServiceTime(rt_faults.slow, service_time)
        wl: Workload = self.plan.workload
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
            # adaptive dummy streaming: pad the stage's collection up to the
            # provisioned collect rate (real + priced dummy), mirroring the
            # flat frontend's deficit injector — phantoms flow exactly when
            # real traffic lags the rate the budget deadline assumes
            target = sum(a.rate + a.dummy for a in s.allocs) if fe.dummies else 0.0
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
                        s_, machines_, timeout, self.policy, dummies=fe.dummies,
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
                relax=(fe.dummies and fe.burst_deadline and timeout == "budget"),
            )
            if service_time is not None:
                # feed every started batch's measured duration to the
                # control plane: epochs replan against corrected profiles
                # and record the model-vs-measured error
                for st in stages.values():
                    st.service_obs = rt.observe_service
        e2e_hint = plan_e2e_hint(self.plan)
        pace = offered_rate if offered_rate is not None else frame_rate
        if ctrl is not None:
            ctrl.reset()
            # admission emits its own decision-resolution telemetry: every
            # denial, with interim retry denials the closed loop later
            # re-admits tagged "shed_retry" (terminal ones "shed"); the
            # loop's terminal emit defers to it (see
            # `pipeline.core.issue_frame`) so sheds are never double-counted
            ctrl.obs = obs
        perf = dict(
            reference=cfg.reference,
            fast_path=cfg.fast_path,
            event_queue=cfg.event_queue,
            quantum=cfg.quantum,
        )
        if fe.clients is not None:
            res = run_pipeline(
                wl.app, stages, n_frames,
                clients=fe.clients, pace=pace, admission=ctrl,
                tail=tail, seed=seed, control=rt, e2e_hint=e2e_hint,
                obs=obs, faults=rt_faults, **perf,
            )
        else:
            issue = make_arrivals(arrivals, n_frames, pace, seed=seed)
            res = run_pipeline(
                wl.app, stages, n_frames,
                issue=issue, admission=ctrl, tail=tail, seed=seed,
                control=rt, e2e_hint=e2e_hint, obs=obs, faults=rt_faults,
                **perf,
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

    def _serve(
        self,
        arrival: np.ndarray,
        shed_mask: np.ndarray,
        frame_rate: float,
        fe: FrontendConfig,
        *,
        timeout: "float | str | None",
        tail: str,
        service_time: "ServiceTimeSource | None" = None,
        obs: "Observability | None" = None,
    ) -> tuple[ServeResult, np.ndarray]:
        """Replay the DAG over admitted frames; returns the result plus the
        per-frame e2e latency array (NaN for shed/dropped frames)."""
        wl: Workload = self.plan.workload
        arrival = np.asarray(arrival, dtype=np.float64)
        n_frames = arrival.size
        # finish time of frame i at module m (0.0 = not processed / dropped)
        finish_at = {m: np.zeros(n_frames) for m in wl.app.modules}
        stats = {m: ModuleStats() for m in wl.app.modules}
        # a frame is *lost* when some module materialized instances for it
        # but completed none (tail drop / deadline overrun) — as opposed to a
        # frame a fanout < 1 module legitimately skipped, which the seed
        # semantics exclude from the statistics entirely
        lost = np.zeros(n_frames, dtype=bool)
        # quiescence-depth tracking (causal tail order): end-of-stream tail
        # flushes happen in the event loop's quiescence rounds, strictly
        # after all normal completions — their backdated cascades must be
        # *delivered* last at DAG joins even when their times are earlier.
        # Only the timeout=None flush path produces tails; the dummy
        # frontend's phantom merge assumes sorted streams, so the (untested)
        # dummies+no-timeout combination keeps the legacy order.
        track_depth = timeout is None and tail == "flush" and not fe.dummies
        depth = (
            {m: np.zeros(n_frames, dtype=np.int64) for m in wl.app.modules}
            if track_depth
            else {}
        )
        emit = (
            {m: np.zeros(n_frames) for m in wl.app.modules}
            if track_depth
            else {}
        )
        tail_rounds: dict[str, int] = {}
        anc = wl.app.ancestor_closure() if track_depth else {}
        for m in topo_sort(wl.app.modules, wl.app.edges):
            parents = wl.app.parents(m)
            in_depth = in_emit = None
            if parents:
                pf = np.stack([finish_at[p] for p in parents])
                ready = np.maximum(arrival, pf.max(axis=0))
                drop = (pf <= 0.0).any(axis=0)
                if track_depth:
                    in_depth, in_emit = lexmax_parents(
                        [depth[p] for p in parents],
                        [emit[p] for p in parents],
                    )
            else:
                ready = arrival
                drop = shed_mask
            fanout = wl.rates[m] / frame_rate
            anc_round = (
                max((tail_rounds.get(a, 0) for a in anc.get(m, ())), default=0)
                if track_depth
                else 0
            )
            tail_rounds[m] = self._run_module(
                m, ready, drop, fanout, finish_at[m], stats[m], lost,
                timeout=timeout, tail=tail, dummies=fe.dummies,
                burst_deadline=fe.burst_deadline,
                service_time=service_time, obs=obs,
                in_depth=in_depth,
                in_emit=in_emit,
                out_depth=depth[m] if track_depth else None,
                out_emit=emit[m] if track_depth else None,
                anc_round=anc_round,
            )
        sinks = [m for m in wl.app.modules if not wl.app.children(m)]
        sf = np.stack([finish_at[s] for s in sinks])
        ok = (sf > 0).all(axis=0)
        lat = np.where(ok, sf.max(axis=0) - arrival, np.nan)
        e2e = lat[ok]
        shed = int(shed_mask.sum())
        dropped = int((lost & ~shed_mask & ~ok).sum())
        return (
            ServeResult(e2e.tolist(), stats, wl.slo, shed=shed, dropped=dropped),
            lat,
        )

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

    def _run_module(
        self,
        m: str,
        ready: np.ndarray,
        drop: np.ndarray,
        fanout: float,
        finish_frame: np.ndarray,
        stats: ModuleStats,
        lost: np.ndarray,
        *,
        timeout: "float | str | None",
        tail: str,
        dummies: bool = False,
        burst_deadline: bool = False,
        service_time: "ServiceTimeSource | None" = None,
        obs: "Observability | None" = None,
        in_depth: "np.ndarray | None" = None,
        in_emit: "np.ndarray | None" = None,
        out_depth: "np.ndarray | None" = None,
        out_emit: "np.ndarray | None" = None,
        anc_round: int = 0,
    ) -> int:
        sched = self.plan.schedules[m]
        machines = expand_machines(list(sched.allocs))
        # expand frames into module-level request instances by fanout, in
        # causal order — (quiescence depth, emit, id); plain stable
        # ready-sort when no upstream tail cascades exist — skipping frames
        # dropped upstream
        order = causal_order(ready, in_depth, in_emit)
        frames = order[~drop[order]]
        instances = expand_fanout(frames, fanout)
        n = instances.size
        if n == 0:
            return 0
        ready_inst = ready[instances]
        phantom = np.zeros(n, dtype=bool)
        ready_all = ready_inst
        if dummies:
            # stream the plan's priced dummy traffic: pad the observed real
            # rate up to the provisioned collection rate with phantoms
            target = sum(a.rate + a.dummy for a in sched.allocs)
            ph = phantom_times(ready_inst, target)
            if ph.size:
                ready_all, phantom = merge_phantoms(ready_inst, ph)
        n_all = ready_all.size
        runs = dispatch_runs(machines, n_all, self.policy)
        w = self._module_timeout(
            m, machines, timeout, dummies=dummies, burst_deadline=burst_deadline
        )
        ex = self.executors.get(m)
        hook = None
        if obs is not None:
            # per-batch telemetry feed for the event-core legs: exact spans
            # (measured durations included) via `events.simulate_module_events`'s
            # passive on_batch observer; the vectorized leg below reports
            # column-level tallies from `ModuleReplay.batches` instead
            def hook(machine: Machine, start: float, end: float, rids,
                     closed: float) -> None:
                reals = [float(ready_all[r]) for r in rids if not phantom[r]]
                obs.batch_start(
                    m, machine.mid, start, end - start, len(rids),
                    machine.config.batch, len(rids) - len(reals),
                )
                obs.waits(
                    m, sum(closed - r for r in reals),
                    len(reals) * (start - closed),
                    len(reals) * (end - start), len(reals),
                )
        if service_time is not None and service_time.kind != "analytic":
            # trace/live durations: the vectorized kernel assumes the
            # profiled constant, so route through the event core's
            # service-time hook (`MachineCore.start`'s duration callable)
            def _sourced(machine: Machine, group: int) -> float:
                return service_time.duration(m, machine, group)

            finish, batches = simulate_module_events(
                machines,
                ready_all,
                runs_to_assignment(runs, n_all),
                timeout=w,
                tail=tail,
                executor=_sourced,
                phantom=phantom,
                on_batch=hook,
            )
            rep = ModuleReplay(finish, runs_to_assignment(runs, n_all), batches, phantom)
        elif ex is None:
            rep = replay_module(
                machines, ready_all, runs, timeout=w, tail=tail, phantom=phantom,
                with_closed=obs is not None,
            )
            if obs is not None:
                done_all = ~np.isnan(rep.finish)
                by_mid = {mm.mid: mm.config for mm in machines}
                obs.bulk_module(
                    m,
                    batches=rep.n_batches,
                    members=int(done_all.sum()),
                    phantoms=int((phantom & done_all).sum()),
                    slots=sum(
                        k * by_mid[mid].batch for mid, k in rep.batches.items()
                    ),
                    busy=sum(
                        k * by_mid[mid].duration
                        for mid, k in rep.batches.items()
                    ),
                )
                obs.waits(m, *rep.waits(ready_all, machines))
        else:
            def _measured(machine: Machine, _group: int) -> float:
                t0 = time.perf_counter()
                ex(machine.config.batch)
                return time.perf_counter() - t0

            finish, batches = simulate_module_events(
                machines,
                ready_all,
                runs_to_assignment(runs, n_all),
                timeout=w,
                tail=tail,
                executor=_measured,
                phantom=phantom,
                on_batch=hook,
            )
            rep = ModuleReplay(finish, runs_to_assignment(runs, n_all), batches, phantom)
        # phantoms fill batches but never enter the statistics; the stable
        # merge preserved real-request order, so slicing by the mask aligns
        # the finish times back with ``ready_inst`` / ``instances``
        tail_round = 0
        if out_depth is not None:
            # thread the quiescence depth through service: completions
            # inherit their machine's running-max arrival depth, this
            # module's own flushed tail (if any) fires one round past the
            # deepest ancestor flush, and each frame's resolve key is the
            # lexicographic (depth, finish) max over its instances — the
            # processing instant of its last completion event
            inst_depth = (
                in_depth[instances]
                if in_depth is not None
                else np.zeros(n, dtype=np.int64)
            )
            out_inst, tail_round = propagate_depth(
                inst_depth, rep.assignment, rep.finish, machines, w, tail,
                anc_round,
            )
            done_i = ~np.isnan(rep.finish)
            lexmax_fold(
                instances[done_i], out_inst[done_i], rep.finish[done_i],
                out_depth, out_emit,
            )
        finish_real = rep.finish[~phantom]
        done = ~np.isnan(finish_real)
        stats.batches += rep.n_batches
        stats.phantom += int(phantom.sum())
        stats.dropped += int(n - done.sum())
        stats.latencies.extend((finish_real[done] - ready_inst[done]).tolist())
        # frame finish = max over its instances (dropped instances contribute 0)
        np.maximum.at(finish_frame, instances[done], finish_real[done])
        # frames that had instances here but completed none are lost, not
        # merely skipped by fanout — they count as pipeline drops
        if not done.all():
            had = np.zeros(finish_frame.size, dtype=bool)
            had[instances] = True
            lost |= had & (finish_frame <= 0.0)
        return tail_round
