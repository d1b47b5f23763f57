"""Unified event-driven serving subsystem.

Layers, ingress to silicon:

* ``arrivals``  — seeded open-loop arrival processes (uniform / poisson /
  bursty MMPP / diurnal trace).
* ``frontend``  — the overload-aware serving frontend: dummy-request
  streaming (the plan's priced phantom traffic joins batch formation, never
  the statistics), admission control (token-bucket / queue-depth shedding at
  ingress, per-app policies), and closed-loop clients (bounded in-flight
  frames, jittered retry-on-shed) as an alternative to open-loop arrivals.
* ``events``    — priority-queue discrete-event core with real tail-batch
  deadline semantics; the single-module reference oracle; its per-machine
  ``MachineCore`` is the composable stage brick.
* ``replay``    — numpy-vectorized per-machine replay kernel (the pipelined
  loop's segment fast path), property-tested against the event core.
* ``engine``    — ``ServingEngine.run`` executes a Harpagon ``Plan`` over a
  frame stream through the pipelined co-simulation (the one served path).
* ``pipeline``  — multi-module pipelined co-simulation: frames traverse the
  DAG as tracked entities, downstream ingress fed by upstream batch
  completions, bounded queues exert backpressure, per-frame fanout can be
  stochastic and sibling-correlated, clients/admission live inside the
  event loop.  ``ServingEngine.run(pipeline=PipelineConfig(...))`` bounds
  queues, draws fanout, or selects the ``reference=True`` oracle loop.
* ``control``   — the incremental control plane: windowed trend-forecast
  rate estimation, warm-start ``Planner.replan`` at every epoch, and
  hot-swap of the resulting ``PlanDelta`` onto the live stages without
  dropping in-flight frames.  Selected via
  ``ServingEngine.run(control=ControlLoopConfig(...))``; the per-epoch
  audit trail is returned as ``ServeResult.epochs``.
* ``service_time`` — pluggable batch service durations: ``analytic``
  (profiled constant, bit-exact default), ``trace`` (recorded samples,
  deterministic replay), ``live`` (real executors timed per batch).
  Selected via ``ServingEngine.run(service_time=...)``; with a control
  loop, observed durations correct the profiles epochs replan against.
* ``observability`` — the passive telemetry layer: a structured trace
  recorder (ring-buffered, deterministically sampled, Perfetto-exportable),
  a per-epoch metrics registry (occupancy / dummy fill / stalls /
  utilization per module), and SLO-miss forensics (every missed or shed
  frame classified into exactly one cause, conservation-checked).
  Selected via ``ServingEngine.run(observability=True)`` (or an
  ``ObservabilityConfig``); results are bit-identical with it on or off.
* ``faults``    — seeded deterministic fault injection (machine crash,
  transient straggler, whole-device loss) firing as events inside the
  pipelined loop, with watchdog-based detection (suspect → dead on missed
  batch heartbeats), frame-conserving re-queue recovery, out-of-band
  failure replans with warm-spare promotion, and allocator repacks on
  shared-device death.  Selected via
  ``ServingEngine.run(faults=FaultConfig(...))``; disabled ⇒ bit-exact
  with the fault-free engine.
* ``tenancy``   — the multi-tenant shared pool: a device-centric plan view
  (`DevicePlan`), a global allocator FFD-packing fractional module residues
  onto shared devices under an interference-aware e2e-SLO guard, and
  `SharedPool` running every app on one consolidated pool with co-located
  batches honestly slowed by a calibrated interference model.
* ``simulator`` — module-level Theorem-1 validation harness.
* ``reference`` — the frozen seed loops (golden equivalence baselines).

Frontend usage sketch::

    from repro.serving import ServingEngine
    from repro.serving.frontend import (
        ClosedLoopClients, FrontendConfig, TokenBucket,
    )

    # stream dummy traffic so a dummy-padded plan meets its modeled WCL
    fe = FrontendConfig(dummies=True)
    ServingEngine(plan).run(2000, rate, timeout="budget", frontend=fe)

    # shed at ingress under MMPP overload: bounded p99, reported shed rate
    fe = FrontendConfig(admission=TokenBucket(burst=4))
    r = ServingEngine(plan).run(
        2000, rate, arrivals="mmpp", offered_rate=1.3 * rate, frontend=fe
    )
    r.shed, r.attainment, r.p99   # shed frames count as SLO misses

    # closed-loop clients: offered load self-throttles under overload
    fe = FrontendConfig(clients=ClosedLoopClients(n_clients=16, retry_on_shed=True))
    ServingEngine(plan).run(2000, rate, frontend=fe)

The default path (no frontend, open-loop arrivals, ``timeout=None``)
reproduces the seed engine numbers exactly (`tests/test_golden_equivalence`).
"""
from .arrivals import (
    ARRIVALS,
    make_arrivals,
    mmpp_arrivals,
    poisson_arrivals,
    trace_arrivals,
    uniform_arrivals,
)
from .control import ControlLoopConfig, ControlRuntime, EpochRecord, serving_cost
from .engine import ModuleStats, ServeResult, ServingEngine
from .events import simulate_module_events
from .faults import FAULT_KINDS, FaultConfig, FaultRuntime
from .frontend import (
    ClosedLoopClients,
    FrontendConfig,
    QueueDepth,
    TokenBucket,
    make_admission,
)
from .observability import (
    MISS_CAUSES,
    MetricsSnapshot,
    MissReport,
    Observability,
    ObservabilityConfig,
    TraceRecorder,
    classify_misses,
)
from .pipeline import FanoutSpec, PipelineConfig, PipelineResult
from .replay import ModuleReplay, expand_fanout, replay_machine, replay_module
from .reference import engine_run_reference, simulate_reference
from .service_time import (
    AnalyticServiceTime,
    DegradedServiceTime,
    InterferenceServiceTime,
    LiveServiceTime,
    ServiceTimeSource,
    TraceServiceTime,
    resolve_service_time,
)
from .simulator import SimResult, simulate
from .tenancy import (
    DevicePlan,
    GlobalAllocator,
    PoolResult,
    SharedPool,
    TenancyConfig,
)

__all__ = [
    "ARRIVALS",
    "AnalyticServiceTime",
    "ClosedLoopClients",
    "ControlLoopConfig",
    "ControlRuntime",
    "DegradedServiceTime",
    "EpochRecord",
    "FanoutSpec",
    "FAULT_KINDS",
    "FaultConfig",
    "FaultRuntime",
    "DevicePlan",
    "FrontendConfig",
    "GlobalAllocator",
    "InterferenceServiceTime",
    "LiveServiceTime",
    "MISS_CAUSES",
    "MetricsSnapshot",
    "MissReport",
    "ModuleReplay",
    "Observability",
    "ObservabilityConfig",
    "PipelineConfig",
    "PipelineResult",
    "ModuleStats",
    "PoolResult",
    "QueueDepth",
    "ServeResult",
    "ServiceTimeSource",
    "ServingEngine",
    "SharedPool",
    "SimResult",
    "TenancyConfig",
    "TokenBucket",
    "TraceRecorder",
    "TraceServiceTime",
    "classify_misses",
    "engine_run_reference",
    "expand_fanout",
    "make_admission",
    "make_arrivals",
    "mmpp_arrivals",
    "poisson_arrivals",
    "replay_machine",
    "replay_module",
    "resolve_service_time",
    "serving_cost",
    "simulate",
    "simulate_module_events",
    "simulate_reference",
    "trace_arrivals",
    "uniform_arrivals",
]
