"""Pluggable service-time sources for the serving co-simulation.

The simulators take a batch's service duration from the plan's profiled
configuration (``machine.config.duration``) — the analytic roofline the
planner optimized against.  A :class:`ServiceTimeSource` makes that choice
explicit and swappable, so the *same* pipelined event loop can co-simulate
against measured executor step times:

* :class:`AnalyticServiceTime` — the profiled constant.  The default
  (``service_time=None``) bypasses the abstraction entirely and is
  **bit-exact** with the pre-existing paths; an explicit analytic source
  routes through the hook but returns the identical float.
* :class:`TraceServiceTime` — recorded per-``(module, batch)`` duration
  sample sequences, consumed in call order (the trace's ``seq`` axis) and
  optionally perturbed by seeded lognormal jitter.  Fully deterministic
  under a fixed seed: per-key RNG streams are derived from
  ``crc32(module) ^ batch`` so replay order across modules cannot leak
  randomness between keys.
* :class:`LiveServiceTime` — actual executor forwards
  (``executors[module](batch_size)``, e.g. the jitted published-width
  forwards of ``repro.launch.serve --real``), timed with
  ``time.perf_counter`` per batch start and cached per ``(module, batch)``
  once ``warmup`` timed calls have retired the jit/compile transient.

Sources are consulted at **batch start** (`events.MachineCore.start`'s
``duration`` callable — the single choke point both the single-module event
core and the pipelined `ModuleStage` drive), so every formation/deadline
decision upstream of service is untouched.  The measured duration of every
started batch can additionally be fed to an observer (the control plane's
`ControlRuntime.observe_service`), which is how epochs replan against
reality instead of the analytic roofline.
"""
from __future__ import annotations

import time
import zlib
from typing import Callable, Mapping, Sequence

import numpy as np

from ..core.dispatch import Machine
from .observability.spans import annotate


def _key_stream(seed: int, module: str, batch: int) -> np.random.Generator:
    """A per-(module, batch) RNG stream, stable across call interleavings."""
    return np.random.default_rng(
        np.random.SeedSequence([seed, zlib.crc32(module.encode("utf-8")), batch])
    )


class ServiceTimeSource:
    """Base protocol: map a batch start to its service duration (seconds).

    ``duration(module, machine, n_members)`` is called once per started
    batch with the full member count (phantom fills included — an executor
    runs the whole batch).  Implementations must be deterministic under
    :meth:`reset` for replayability; the base class is the analytic
    semantics itself.
    """

    kind = "analytic"

    def duration(self, module: str, machine: Machine, n_members: int) -> float:
        return machine.config.duration

    def reset(self) -> None:
        """Rewind any per-run state (sample cursors, RNG streams, caches)."""


class AnalyticServiceTime(ServiceTimeSource):
    """The profiled configuration duration — identical to the default path."""


class TraceServiceTime(ServiceTimeSource):
    """Replay recorded duration samples deterministically.

    ``samples`` maps ``(module, batch) -> [d0, d1, ...]`` — or, on
    heterogeneous pools where the same batch size runs on several hardware
    tiers, ``(module, batch, hardware)``; ``module -> [...]`` is a
    batch-agnostic fallback.  The k-th started batch of a key takes sample
    ``k mod len`` — the trace's sequence axis.  Keys with no samples fall
    back to the profiled duration.  ``jitter`` (relative
    sigma) multiplies each draw by a lognormal factor from the key's own
    seeded stream, so two runs with the same seed are bit-identical
    regardless of how other modules' calls interleave.
    """

    kind = "trace"

    def __init__(
        self,
        samples: "Mapping[tuple[str, int] | str, Sequence[float]]",
        *,
        jitter: float = 0.0,
        seed: int = 0,
    ):
        if jitter < 0.0:
            raise ValueError("jitter must be >= 0")
        self.samples = {
            k: [float(d) for d in v] for k, v in samples.items()
        }
        for k, v in self.samples.items():
            if any(d <= 0.0 for d in v):
                raise ValueError(f"trace durations must be positive ({k!r})")
        self.jitter = float(jitter)
        self.seed = int(seed)
        self.reset()

    def reset(self) -> None:
        self._pos: dict[tuple[str, int], int] = {}
        self._rng: dict[tuple[str, int], np.random.Generator] = {}

    def duration(self, module: str, machine: Machine, n_members: int) -> float:
        b = machine.config.batch
        key = (module, b, machine.config.hardware)
        seq = self.samples.get(key)
        if seq is None:
            key = (module, b)
            seq = self.samples.get(key)
        if seq is None:
            seq = self.samples.get(module)
        if seq:
            i = self._pos.get(key, 0)
            self._pos[key] = i + 1
            d = seq[i % len(seq)]
        else:
            d = machine.config.duration
        if self.jitter > 0.0:
            rng = self._rng.get(key)
            if rng is None:
                rng = self._rng[key] = _key_stream(self.seed, module, b)
            d *= float(np.exp(self.jitter * rng.standard_normal()))
        return d


class InterferenceServiceTime(ServiceTimeSource):
    """Stretch specific machines' durations by co-location slowdown factors.

    ``factors`` maps ``(module, machine_id) -> multiplicative slowdown``
    (>= 1.0) for the residue machines the tenancy allocator packed onto a
    shared device; every other machine runs at the underlying duration.
    The mapping is read *live* on every batch start, so the shared-pool
    runtime can mutate it in place when an epoch repack changes who a
    machine is co-resident with (hot-swapped device plans).

    ``base`` is an optional wrapped source (trace / live measurements);
    ``None`` stretches the profiled constant.  ``kind`` is non-analytic on
    purpose: a co-located tail is *not* the profiled constant the
    vectorized flat kernel replays, so eligible runs stay on the event
    loop where per-machine durations are honored.
    """

    kind = "interference"

    def __init__(
        self,
        factors: "Mapping[tuple[str, int], float]",
        base: "ServiceTimeSource | None" = None,
    ):
        for k, s in factors.items():
            if s < 1.0 - 1e-12:
                raise ValueError(f"slowdown factors must be >= 1 ({k!r}: {s})")
        # held by reference, never copied: the shared-pool repack hook
        # mutates the caller's mapping in place and the next batch start
        # must see the post-repack slowdowns
        self.factors = factors
        self.base = base

    def duration(self, module: str, machine: Machine, n_members: int) -> float:
        d = (
            self.base.duration(module, machine, n_members)
            if self.base is not None
            else machine.config.duration
        )
        return d * self.factors.get((module, machine.mid), 1.0)

    def reset(self) -> None:
        if self.base is not None:
            self.base.reset()


class DegradedServiceTime(ServiceTimeSource):
    """Stretch straggling machines' durations by live fault slowdowns.

    ``slow`` is the fault injector's straggler table
    (`faults.FaultRuntime.slow`), held **by reference**: a ``straggler``
    fault entering a ``(module, machine_id)`` key inflates that machine's
    service durations mid-run, and the recovery event removing the key
    restores them — no stage or plan state is touched.  ``base`` is the
    run's underlying source (trace / live / interference); ``None``
    stretches the profiled constant.

    ``kind`` is non-analytic on purpose: a straggling machine is not the
    profiled constant the vectorized flat kernel replays, so fault runs
    stay on the event loop where per-machine durations are honored.  An
    empty table is a pure pass-through — with the injector disabled the
    wrapper is never installed at all, keeping the default path bit-exact.
    """

    kind = "degraded"

    def __init__(
        self,
        slow: "Mapping[tuple[str, int], float]",
        base: "ServiceTimeSource | None" = None,
    ):
        # held by reference, never copied: the fault runtime mutates the
        # table in place as stragglers come and go
        self.slow = slow
        self.base = base

    def duration(self, module: str, machine: Machine, n_members: int) -> float:
        d = (
            self.base.duration(module, machine, n_members)
            if self.base is not None
            else machine.config.duration
        )
        return d * self.slow.get((module, machine.mid), 1.0)

    def reset(self) -> None:
        if self.base is not None:
            self.base.reset()


class LiveServiceTime(ServiceTimeSource):
    """Measure real executor forwards, cache steady-state per (module, batch).

    Each consulted batch runs ``executors[module](batch_size)`` and times it.
    The first ``warmup`` timed calls of a key are treated as the jit/compile
    transient; once a key has ``warmup + 1`` measurements, the mean of the
    post-warmup ones is cached and returned without re-executing (the
    co-simulation then advances at recorded wall-clock speed).  A module
    without an executor is an error, never the profiled duration: a live
    run that silently mixed measured and modeled times would report the
    model as a measurement.  ``cache=False``
    re-measures every batch (honest but slow — every simulated batch is a
    real forward).  Each executor call is a ``step <module> b<batch>``
    profiler step (`observability.spans.annotate`), numbered by the calls
    made; it feeds no counter.
    """

    kind = "live"

    def __init__(
        self,
        executors: Mapping[str, Callable[[int], None]],
        *,
        warmup: int = 1,
        cache: bool = True,
    ):
        if warmup < 0:
            raise ValueError("warmup must be >= 0")
        self.executors = dict(executors)
        for module, ex in self.executors.items():
            # an executor that names itself spans its calls under that name
            name = getattr(ex, "name", module)
            if name != module:
                raise ValueError(
                    f"live service time: the executor of module {module!r} "
                    f"is named {name!r}; its spans would land on another row"
                )
        self.warmup = int(warmup)
        self.cache = bool(cache)
        self.reset()

    def reset(self) -> None:
        self.measured: dict[tuple[str, int], list[float]] = {}
        self._cached: dict[tuple[str, int], float] = {}
        self._steps = 0  # executor calls made: each one's profiler step number

    def duration(self, module: str, machine: Machine, n_members: int) -> float:
        b = machine.config.batch
        key = (module, b)
        hit = self._cached.get(key)
        if hit is not None:
            return hit
        ex = self.executors.get(module)
        if ex is None:
            raise KeyError(
                f"live service time: no executor for module {module!r} "
                f"(executors: {sorted(self.executors)})"
            )
        self._steps += 1
        with annotate("step", module, b, step=self._steps):
            t0 = time.perf_counter()
            ex(b)
            d = time.perf_counter() - t0
        obs = self.measured.setdefault(key, [])
        obs.append(d)
        if self.cache and len(obs) > self.warmup:
            steady = obs[self.warmup:]
            self._cached[key] = sum(steady) / len(steady)
        return d

    def to_trace(self, *, jitter: float = 0.0, seed: int = 0) -> TraceServiceTime:
        """Freeze the measurements into a replayable trace (post-warmup)."""
        samples = {
            k: v[self.warmup:] or v for k, v in self.measured.items() if v
        }
        return TraceServiceTime(samples, jitter=jitter, seed=seed)


def resolve_service_time(
    spec: "str | ServiceTimeSource | None",
    executors: "Mapping[str, Callable[[int], None]] | None" = None,
) -> "ServiceTimeSource | None":
    """Normalize a ``run(service_time=...)`` spec.

    ``None`` / ``"analytic"`` resolve to ``None`` — the untouched (bit-exact)
    default path.  ``"live"`` wraps the engine's executors; ``"trace"``
    cannot be named by string (a trace needs its samples — pass a
    `TraceServiceTime`).
    """
    if spec is None or spec == "analytic":
        return None
    if spec == "live":
        if not executors:
            raise ValueError(
                'service_time="live" requires executors '
                "(ServingEngine(..., executors=...))"
            )
        return LiveServiceTime(executors)
    if spec == "trace":
        raise ValueError(
            'service_time="trace" needs its samples: pass a '
            "TraceServiceTime(samples, ...) instance"
        )
    if isinstance(spec, ServiceTimeSource):
        return spec
    raise TypeError(f"unknown service_time spec {spec!r}")
