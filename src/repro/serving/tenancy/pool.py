"""Shared-pool serving: every app on one consolidated device pool.

`SharedPool` is the engine wiring of the tenancy layer: it takes the
per-app module-centric plans, derives the pool's :class:`DevicePlan`
through the :class:`GlobalAllocator`, and runs each app's serving loop
with interference folded into the co-located machines' service durations
(`InterferenceServiceTime` — co-located batches honestly run slower).

Cost accounting is the point: `PoolResult.savings` compares the pool's
integer-device bill against ``dedicated_cost`` — the sum of per-app
exclusive deployments with every fractional allocation rounded up to
whole devices.  Attainment is measured per app by the same simulators a
dedicated deployment would use, so "cheaper at equal attainment" is an
apples-to-apples claim.

Control-plane arbitration: with ``control=`` each app's `ControlRuntime`
gets an ``on_swap`` hook; every committed plan hot-swap resubmits the
tenant's new plan to the global allocator, which repacks the pool,
updates the app's live interference factors in place (the service-time
source reads them per batch start), and emits ``colocate`` / ``evict``
instants plus per-device occupancy counters to the pool's trace.  App
loops co-simulate sequentially over the same simulated horizon, so a
repack triggered by app A is visible to apps run after it and to A's own
remaining batches; it does not retroactively slow batches an earlier
tenant's finished run already recorded — the epoch-synchronous
approximation of a fully interleaved pool.

Tenancy off (``tenancy=None``) degrades to per-app dedicated serving
with no interference and no shared devices: results are bit-exact with
`ServingEngine.run` per app (pinned by ``tests/test_tenancy.py``).
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace as dc_replace
from typing import Mapping

from ...core.harpagon import Plan
from ...profiling.interference import InterferenceModel, calibrate
from ..control import ControlLoopConfig
from ..engine import ServeResult, ServingEngine
from ..faults import FaultConfig
from ..observability import Observability
from ..service_time import InterferenceServiceTime, resolve_service_time
from .allocator import AllocatorConfig, GlobalAllocator, dedicated_cost
from .device import DevicePlan, DevicePlanDelta


@dataclass(frozen=True)
class TenancyConfig:
    """Pool-level knobs: the interference model and the packing rules.

    ``interference=None`` calibrates the default model from ``seed``
    (`profiling.interference.calibrate` — deterministic).  The remaining
    fields forward to :class:`AllocatorConfig`; ``guard=False`` packs
    purely by capacity (useful to measure what the feasibility guard is
    buying), ``slo_slack`` tightens (<1) or loosens (>1) the guard's
    end-to-end latency ceiling.
    """

    interference: "InterferenceModel | None" = None
    seed: int = 0
    max_coresident: int = 2
    occupancy_cap: float = 1.0
    guard: bool = True
    slo_slack: float = 1.0

    def model(self) -> InterferenceModel:
        return self.interference or calibrate(seed=self.seed)

    def allocator(self) -> GlobalAllocator:
        return GlobalAllocator(
            AllocatorConfig(
                interference=self.model(),
                max_coresident=self.max_coresident,
                occupancy_cap=self.occupancy_cap,
                guard=self.guard,
                slo_slack=self.slo_slack,
            )
        )


@dataclass
class PoolResult:
    """Per-app serve results plus the pool-level consolidation ledger."""

    results: "dict[str, ServeResult]"
    device_plan: DevicePlan
    dedicated_cost: float
    n_frames: "dict[str, int]"
    repacks: "list[DevicePlanDelta]" = field(default_factory=list)
    trace: "object | None" = None  # pool-level TraceRecorder (colocate/evict)

    @property
    def pool_cost(self) -> float:
        return self.device_plan.cost

    @property
    def savings(self) -> float:
        """How much cheaper the shared pool is than dedicated deployments."""
        return self.dedicated_cost / self.pool_cost if self.pool_cost else 1.0

    @property
    def offered(self) -> int:
        return sum(r.offered for r in self.results.values())

    @property
    def attainment(self) -> float:
        """Offered-frame-weighted aggregate SLO attainment across apps."""
        total = self.offered
        if total == 0:
            return 1.0
        return sum(
            r.attainment * r.offered for r in self.results.values()
        ) / total

    def conservation(self) -> "dict[str, bool]":
        """Per-app frame accounting: every issued frame resolved terminally.

        completed + shed + dropped (+ skipped, for frames a zero-instance
        fanout legitimately excluded from the sink) == frames issued."""
        out: dict[str, bool] = {}
        for app, r in self.results.items():
            p = r.pipeline
            resolved = int(
                p.completed.sum() + p.shed.sum() + p.dropped.sum()
                + p.skipped.sum()
            )
            out[app] = resolved == self.n_frames[app]
        return out

    def summary(self) -> str:
        lines = [
            f"pool: cost={self.pool_cost:.4g} dedicated={self.dedicated_cost:.4g}"
            f" savings={self.savings:.3f}x attainment={self.attainment:.4f}"
            f" devices={len(self.device_plan.devices)}"
            f" shared={self.device_plan.n_shared} repacks={len(self.repacks)}"
        ]
        for app, r in sorted(self.results.items()):
            lines.append(
                f"  {app}: offered={r.offered} attainment={r.attainment:.4f}"
                f" p99={r.p99:.3f} shed={r.shed} dropped={r.dropped}"
            )
        return "\n".join(lines)


class SharedPool:
    """One machine pool serving every app's plan (see module docstring)."""

    def __init__(
        self,
        plans: "Mapping[str, Plan]",
        *,
        tenancy: "TenancyConfig | None" = TenancyConfig(),
        executors: "Mapping[str, Mapping] | None" = None,
    ):
        if not plans:
            raise ValueError("SharedPool needs at least one app plan")
        for app, plan in plans.items():
            if plan.workload.app.name != app:
                raise ValueError(
                    f"plans key {app!r} does not match its workload app "
                    f"{plan.workload.app.name!r}"
                )
        self.plans = dict(plans)
        self.tenancy = tenancy
        self.executors = executors or {}
        if tenancy is not None:
            self.model = tenancy.model()
            self.allocator = tenancy.allocator()
            self.device_plan = self.allocator.pack(self.plans)
        else:
            # disabled: every machine keeps its own device, no interference
            self.model = None
            self.allocator = GlobalAllocator(
                AllocatorConfig(interference=None, max_coresident=1)
            )
            self.device_plan = self.allocator.pack(self.plans)
        self.dedicated_cost = dedicated_cost(self.plans)

    @property
    def enabled(self) -> bool:
        return self.tenancy is not None

    def _emit_pack(self, obs: "Observability | None", t: float,
                   dp: DevicePlan) -> None:
        if obs is None:
            return
        for d in dp.devices:
            if d.shared:
                for s in d.slots:
                    obs.colocate(t, d.did, s.app, s.module, s.mid, s.fraction)
            obs.device_occupancy(t, d.did, d.occupancy)

    def _emit_delta(self, obs: "Observability | None", t: float,
                    dp: DevicePlan, delta: DevicePlanDelta) -> None:
        if obs is None or delta.empty:
            return
        for did, (app, module, mid) in delta.evicted:
            obs.evict(t, did, app, module, mid)
        for did, (app, module, mid) in delta.colocated:
            d = dp.devices[did] if did < len(dp.devices) else None
            frac = 0.0
            if d is not None:
                for s in d.slots:
                    if s.key == (app, module, mid):
                        frac = s.fraction
                        break
            obs.colocate(t, did, app, module, mid, frac)
        for d in dp.devices:
            obs.device_occupancy(t, d.did, d.occupancy)

    def _frame_rate(self, app: str,
                    frame_rates: "Mapping[str, float] | float | None") -> float:
        if isinstance(frame_rates, Mapping):
            return float(frame_rates[app])
        if frame_rates is not None:
            return float(frame_rates)
        # derive from the workload: the DAG's first module is the source
        # and carries the app-level frame rate (fanout 1.0 by convention)
        wl = self.plans[app].workload
        return float(wl.rates[wl.app.modules[0]])

    def run(
        self,
        n_frames: "int | Mapping[str, int]",
        frame_rates: "Mapping[str, float] | float | None" = None,
        *,
        arrivals="uniform",
        seed: int = 0,
        timeout=None,
        tail: str = "flush",
        frontend=None,
        offered_rates: "Mapping[str, float] | None" = None,
        control: "ControlLoopConfig | Mapping[str, ControlLoopConfig] | None" = None,
        service_time=None,
        observability=None,
        faults: "FaultConfig | Mapping[str, FaultConfig] | None" = None,
    ) -> PoolResult:
        """Serve every app of the pool over one simulated horizon.

        Arguments mirror `ServingEngine.run`; per-app values may be given
        as mappings keyed by app name (``n_frames``, ``frame_rates``,
        ``offered_rates``, ``control``, ``faults``).  Each app's arrival
        stream is seeded with ``seed + its rank`` in sorted-app order, so
        streams are distinct but the whole pool run is deterministic.
        ``observability`` builds one pool-level sink (colocate/evict
        instants, occupancy counters — returned as ``PoolResult.trace``)
        and an independent per-app sink per run (on each `ServeResult`).

        ``faults`` arms the seeded injector inside each app's loop; with
        tenancy enabled the config is wired to the pool before the run —
        the app's machine slots are mapped to their physical devices (as
        packed at run start), so a ``device_loss`` fault takes down every
        co-located slot of one device at once and triggers the allocator's
        out-of-band `GlobalAllocator.fail_device` repack (evicted residues
        re-homed onto surviving devices, interference factors refreshed).
        """
        pool_obs = Observability.make(observability)
        dp = self.device_plan
        self._emit_pack(pool_obs, 0.0, dp)
        results: dict[str, ServeResult] = {}
        frames: dict[str, int] = {}
        repacks: list[DevicePlanDelta] = []
        for rank, app in enumerate(sorted(self.plans)):
            plan = self.plans[app]
            n = n_frames[app] if isinstance(n_frames, Mapping) else int(n_frames)
            frames[app] = n
            rate = self._frame_rate(app, frame_rates)
            base = resolve_service_time(
                service_time, self.executors.get(app)
            )
            factors: dict[tuple[str, int], float] = {}
            if self.enabled:
                factors.update({
                    (m, mid): f
                    for (a, m, mid), f in self.device_plan.interference_factors(
                        self.model, app
                    ).items()
                })
            app_control = (
                control.get(app) if isinstance(control, Mapping) else control
            )
            src = base
            if self.enabled and (factors or app_control is not None):
                # live factors: an epoch repack mutates the dict in place
                # and the next batch start reads the new slowdown
                src = InterferenceServiceTime(factors, base=base)
            if app_control is not None and self.enabled:
                def _on_swap(t, new_plan, _app=app, _factors=factors,
                             _obs=pool_obs):
                    new_dp, delta = self.allocator.submit(_app, new_plan)
                    self.device_plan = new_dp
                    self.plans[_app] = new_plan
                    _factors.clear()
                    _factors.update({
                        (m, mid): f
                        for (a, m, mid), f in new_dp.interference_factors(
                            self.model, _app
                        ).items()
                    })
                    repacks.append(delta)
                    self._emit_delta(_obs, t, new_dp, delta)
                app_control = dc_replace(app_control, on_swap=_on_swap)
            app_faults = (
                faults.get(app) if isinstance(faults, Mapping) else faults
            )
            if app_faults is not None and self.enabled:
                # wire the injector to the pool: this app's machine slots
                # mapped to their physical devices (run-start packing), and
                # the allocator's out-of-band device-death repack — the
                # hardware monitor's signal, fired at the injection instant
                device_map = {
                    (s.module, s.mid): d.did
                    for d in self.device_plan.devices
                    for s in d.slots
                    if s.app == app
                }
                def _on_loss(t, dead_did, _app=app, _factors=factors,
                             _obs=pool_obs):
                    new_dp, delta = self.allocator.fail_device(dead_did)
                    self.device_plan = new_dp
                    _factors.clear()
                    _factors.update({
                        (m, mid): f
                        for (a, m, mid), f in new_dp.interference_factors(
                            self.model, _app
                        ).items()
                    })
                    repacks.append(delta)
                    self._emit_delta(_obs, t, new_dp, delta)
                app_faults = dc_replace(
                    app_faults, device_map=device_map, on_device_loss=_on_loss
                )
            eng = ServingEngine(plan, executors=self.executors.get(app))
            results[app] = eng.run(
                n,
                rate,
                arrivals=arrivals,
                seed=seed + rank,
                timeout=timeout,
                tail=tail,
                frontend=frontend,
                offered_rate=(
                    offered_rates.get(app) if offered_rates else None
                ),
                control=app_control,
                service_time=src,
                observability=observability,
                faults=app_faults,
            )
        return PoolResult(
            results=results,
            device_plan=self.device_plan,
            dedicated_cost=self.dedicated_cost,
            n_frames=frames,
            repacks=repacks,
            trace=pool_obs.trace if pool_obs is not None else None,
        )


__all__ = ["PoolResult", "SharedPool", "TenancyConfig"]
