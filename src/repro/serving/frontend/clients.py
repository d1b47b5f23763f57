"""Closed-loop clients: arrivals driven by completions, not by a clock.

Open-loop arrival processes keep coming no matter how slow the service is,
which is the right model for camera feeds but the wrong one for interactive
clients.  A closed-loop client holds at most ``max_in_flight`` frames
outstanding and issues the next one only after a completion (plus think
time), so offered load self-throttles under overload — the classic
closed-vs-open distinction in serving benchmarks.

The pipelined co-simulation (`repro.serving.pipeline.core.run_pipeline`)
runs the client slots inside its event loop: a slot issues a frame, the
admission controller (if any) admits or sheds it at the issue instant
against live backlog, and the slot issues again ``think`` after the frame
actually resolves.  A shed frame is retried with exponentially-jittered
backoff (when enabled) until ``max_retries`` is exhausted; an exhausted
frame is recorded as ``dropped`` with a ``retry_exhausted`` trace cause
(distinct from a first-sight terminal ``shed``).  The bound exists so a
dead or unrecovered stage can't spin the shed→retry loop forever: every
frame leaves the system in bounded attempts.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ClosedLoopClients:
    """Closed-loop arrival mode configuration.

    ``n_clients * max_in_flight`` independent slots share one global frame
    counter; ``think_time`` is the mean pause between a completion and the
    next issue (``think_dist="exp"`` for exponential, ``"const"`` for fixed).
    """

    n_clients: int = 8
    max_in_flight: int = 1
    think_time: float = 0.0
    think_dist: str = "exp"
    retry_on_shed: bool = False
    max_retries: int = 3
    backoff: "float | None" = 0.05
    #   base retry backoff, doubled per attempt, jittered.  ``None`` = re-read
    #   the LIVE plan's modeled end-to-end latency at every retry (per-epoch
    #   state under a control loop, the plan's otherwise): a shed client
    #   waits about one service round of the plan that is actually serving,
    #   not a run-constant guess

    def __post_init__(self):
        if self.n_clients < 1 or self.max_in_flight < 1:
            raise ValueError("need n_clients >= 1 and max_in_flight >= 1")
        if self.think_dist not in ("exp", "const"):
            raise ValueError(f"unknown think_dist {self.think_dist!r}")
        if self.backoff is not None and self.backoff < 0.0:
            raise ValueError("backoff must be >= 0 (or None for live latency)")
