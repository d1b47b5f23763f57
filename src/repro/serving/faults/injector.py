"""The seeded fault injector and the failure-detection state machine."""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Mapping

import numpy as np

# taxonomy — see the package docstring for semantics
FAULT_KINDS = ("crash", "straggler", "device_loss")


@dataclass(frozen=True)
class FaultConfig:
    """Knobs for ``ServingEngine.run(..., faults=...)``.

    ``mtbf`` arms an exponential fault process (mean seconds between
    faults, first draw at ``start``); ``schedule`` lists explicit
    ``(time, kind)`` pairs that fire deterministically (benchmarks use it
    for the one-crash-per-epoch grid).  Both may be combined: the
    schedule drains first, then the MTBF chain takes over.  With neither
    set the config is disabled and the run is bit-exact with
    ``faults=None``.

    ``kinds`` is the taxonomy the MTBF chain draws from (uniform over
    the tuple); ``detect_k`` the watchdog multiplier — a machine whose
    closed batch has not completed ``detect_k ×`` its modeled service
    duration after close is declared suspect, and dead one missed
    heartbeat later.  ``spare`` keeps the most-recently-drained machine
    of each stage idle-warm for one epoch instead of retiring it
    (failover promotes it without a cold add).  ``straggler_factor`` /
    ``straggler_duration`` shape the transient-slowdown fault.

    ``device_map`` / ``on_device_loss`` are not user knobs: the shared
    pool injects them per app (machine slot → physical device id, and
    the allocator repack callback) so a ``device_loss`` fault can take
    down every co-located slot at once.
    """

    mtbf: "float | None" = None
    schedule: "tuple[tuple[float, str], ...]" = ()
    kinds: "tuple[str, ...]" = ("crash",)
    seed: int = 0
    start: float = 0.0
    detect_k: float = 4.0
    spare: bool = True
    straggler_factor: float = 4.0
    straggler_duration: float = 0.5
    # shared-pool wiring (injected via dataclasses.replace, not by users)
    device_map: "Mapping[tuple[str, int], int] | None" = field(
        default=None, compare=False
    )
    on_device_loss: "Callable[[float, int], None] | None" = field(
        default=None, compare=False
    )

    def __post_init__(self):
        if self.mtbf is not None and self.mtbf <= 0.0:
            raise ValueError("mtbf must be positive")
        if self.detect_k <= 1.0:
            raise ValueError("detect_k must exceed 1 (a modeled service)")
        if self.straggler_factor <= 1.0:
            raise ValueError("straggler_factor must exceed 1")
        if self.straggler_duration <= 0.0:
            raise ValueError("straggler_duration must be positive")
        for k in self.kinds:
            if k not in FAULT_KINDS:
                raise ValueError(f"unknown fault kind {k!r}; have {FAULT_KINDS}")
        for t, k in self.schedule:
            if k not in FAULT_KINDS:
                raise ValueError(f"unknown fault kind {k!r} in schedule")
            if t < 0.0:
                raise ValueError("schedule times must be >= 0")

    @property
    def enabled(self) -> bool:
        """True when the injector will actually fire anything."""
        return self.mtbf is not None or bool(self.schedule)


class FaultRuntime:
    """Per-run injector + detector state, driven by the pipelined loop.

    The loop primes one fault event from :meth:`next_fault`, and each
    fired fault chains the next.  ``slow`` is the live straggler table —
    `service_time.DegradedServiceTime` holds it by reference, so entering
    and leaving it changes batch durations mid-run without touching the
    stages.  The detector state (``_suspect``) backs the suspect→dead
    escalation: :meth:`escalate` is called on a missed watchdog
    heartbeat, :meth:`clear` when a completion proves the machine alive.
    """

    def __init__(self, cfg: FaultConfig):
        self.cfg = cfg
        self.rng = np.random.default_rng(cfg.seed)
        self._sched = deque(sorted(cfg.schedule))
        self.slow: dict[tuple[str, int], float] = {}
        self._suspect: set[tuple[str, int]] = set()
        # machines already declared dead: makes the declaration idempotent
        # under stale watchdog events (the core object outlives its verdict
        # until the next stage update retires it)
        self.dead: set[tuple[str, int]] = set()
        # counters surfaced on ServeResult.faults
        self.n_injected = 0
        self.n_killed = 0
        self.n_requeued = 0

    def next_fault(self, t: float) -> "tuple[float, str] | None":
        """The next fault instant/kind at or after ``t`` (None: no more)."""
        if self._sched:
            ft, kind = self._sched.popleft()
            return max(ft, t), kind
        if self.cfg.mtbf is not None:
            dt = float(self.rng.exponential(self.cfg.mtbf))
            return max(t, self.cfg.start) + dt, self._draw_kind()
        return None

    def _draw_kind(self) -> str:
        kinds = self.cfg.kinds
        if len(kinds) == 1:
            return kinds[0]
        return kinds[int(self.rng.integers(len(kinds)))]

    def pick(self, candidates: "list"):
        """Deterministic victim draw over a caller-sorted candidate list."""
        if not candidates:
            return None
        return candidates[int(self.rng.integers(len(candidates)))]

    # -- suspect -> dead escalation (batch-duration watchdog) ----------------
    def escalate(self, module: str, mid: int) -> str:
        """One missed heartbeat: returns ``"suspect"`` first, ``"dead"``
        on the next miss while still suspect."""
        key = (module, mid)
        if key in self._suspect:
            return "dead"
        self._suspect.add(key)
        return "suspect"

    def clear(self, module: str, mid: int) -> None:
        """A completed batch proves the machine alive — drop suspicion."""
        self._suspect.discard((module, mid))

    def forget(self, module: str, mid: int) -> None:
        """The machine is gone (dead or retired): drop all its state."""
        self._suspect.discard((module, mid))
        self.slow.pop((module, mid), None)


__all__ = ["FAULT_KINDS", "FaultConfig", "FaultRuntime"]
