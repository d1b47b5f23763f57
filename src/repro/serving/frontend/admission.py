"""Ingress admission control: shed frames *before* they enter the pipeline.

Under overload (bursty MMPP arrivals at or above the provisioned rate) the
PR-1 simulator's queues — and therefore p99 — grow without bound, because
Harpagon paces machines with zero slack.  A real serving frontend sheds at
ingress instead ("No DNN Left Behind" / OCTOPINF): a bounded admitted rate
keeps queueing delay bounded, trading a shed-rate for a p99 guarantee.

Policies (resolved per app via :func:`make_admission`):

* ``None`` / ``"none"``      — admit everything (PR-1 behavior).
* :class:`TokenBucket`       — sustained ``rate`` frames/s with ``burst``
  bucket depth; admitted traffic over any window ``[t, t+w]`` is bounded by
  ``rate * w + burst``.
* :class:`QueueDepth`        — shed when the source stages already hold
  ``depth`` instances waiting to start service (the classic bounded-buffer
  frontend, against the live backlog of the pipelined loop).

Controllers are *stateful sequential* objects: `admit_live(t, backlog)` must
be called in non-decreasing time order (the pipelined loop calls it at each
issue instant of its event clock).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Mapping, Union


@dataclass(frozen=True)
class TokenBucket:
    """Token-bucket shedding: ``rate`` frames/s sustained, ``burst`` depth.

    ``rate=None`` binds to the provisioned frame rate at engine time — the
    natural operating point: admit exactly what the plan paid machines for.
    """

    rate: float | None = None
    burst: float = 8.0


@dataclass(frozen=True)
class QueueDepth:
    """Bounded ingress queue: shed when ``depth`` instances are waiting at
    the source stages (formation + queued + parked)."""

    depth: int = 16


AdmissionPolicy = Union[None, str, TokenBucket, QueueDepth]


class AdmissionController:
    """Sequential admission over a time-ordered frame stream."""

    def __init__(self, policy: "TokenBucket | QueueDepth", frame_rate: float):
        if frame_rate <= 0:
            raise ValueError("frame_rate must be positive")
        self.policy = policy
        self.frame_rate = frame_rate
        if isinstance(policy, TokenBucket):
            self._rate = policy.rate if policy.rate is not None else frame_rate
            if self._rate <= 0 or policy.burst < 1.0:
                raise ValueError("token bucket needs rate>0 and burst>=1")
        elif isinstance(policy, QueueDepth):
            if policy.depth < 1:
                raise ValueError("queue-depth needs depth>=1")
        else:
            raise TypeError(f"unknown admission policy {policy!r}")
        # passive telemetry sink (`observability.Observability`): the engine
        # wires it before run_pipeline, so every admission denial lands in
        # the trace/metrics at decision resolution.  Closed-loop
        # interim denials the client will re-issue carry the distinct
        # "shed_retry" cause, so summing "shed" instants always equals
        # terminal sheds; the pipelined loop's terminal shed emit defers
        # to a wired controller to avoid double counts.  Survives
        # reset() — a reset clears admission state, not the observer.
        self.obs = None
        self.reset()

    def rebind(self, frame_rate: float) -> None:
        """Re-read the provisioned frame rate (control-plane plan hot-swap).

        A token bucket whose ``rate`` is ``None`` is bound to the
        *provisioned* rate; under an epoch-based control loop that rate is
        per-epoch plan state, not a run constant.  Rebinding preserves the
        live bucket level — only the refill pace follows the new plan.
        Explicit numeric rates are pinned by the operator and do not move.
        """
        if frame_rate <= 0:
            raise ValueError("frame_rate must be positive")
        self.frame_rate = frame_rate
        if isinstance(self.policy, TokenBucket) and self.policy.rate is None:
            self._rate = frame_rate

    def reset(self) -> None:
        """Restore initial state (full bucket, zero tallies)."""
        self.admitted = 0
        self.shed = 0
        if isinstance(self.policy, TokenBucket):
            self._tokens = float(self.policy.burst)
            self._last: float | None = None

    def admit(self, t: float, cause: str = "shed") -> bool:
        """Token-bucket decision for one frame arriving at time ``t``
        (non-decreasing).

        ``cause`` labels the observer instant emitted on denial — callers
        that will re-issue a denied frame pass a non-terminal cause.
        """
        if not isinstance(self.policy, TokenBucket):
            raise TypeError("admit() is the token bucket's; use admit_live()")
        if self._last is not None:
            self._tokens = min(
                float(self.policy.burst),
                self._tokens + (t - self._last) * self._rate,
            )
        self._last = t
        if self._tokens >= 1.0 - 1e-12:
            self._tokens -= 1.0
            self.admitted += 1
            return True
        self.shed += 1
        if self.obs is not None:
            self.obs.shed(t, cause)
        return False

    def admit_live(self, t: float, backlog: int, cause: str = "shed") -> bool:
        """Admit or shed against *live* pipeline state (event-interleaved).

        ``backlog`` is the caller-observed ingress occupancy at time ``t`` —
        the pipelined engine passes the number of source-stage *instances*
        waiting to start service (formation + queued + parked; equal to
        frames whenever the source fanout is 1, as in every seed app).  A
        :class:`TokenBucket` is purely time-based and delegates to
        :meth:`admit`; a :class:`QueueDepth` policy compares this real
        occupancy against ``depth``.
        """
        if isinstance(self.policy, TokenBucket):
            return self.admit(t, cause)
        if backlog >= self.policy.depth:
            self.shed += 1
            if self.obs is not None:
                self.obs.shed(t, cause)
            return False
        self.admitted += 1
        return True


def make_admission(
    spec: "AdmissionPolicy | Mapping[str, AdmissionPolicy]",
    app_name: str,
    frame_rate: float,
) -> AdmissionController | None:
    """Resolve an admission spec (possibly a per-app mapping) to a controller.

    A mapping is keyed by app name with an optional ``"default"`` entry;
    string shorthands ``"none" | "token_bucket" | "queue_depth"`` select the
    default-parameter policies.
    """
    if isinstance(spec, Mapping):
        spec = spec.get(app_name, spec.get("default"))
    if spec is None or spec == "none":
        return None
    if isinstance(spec, str):
        try:
            spec = {"token_bucket": TokenBucket(), "queue_depth": QueueDepth()}[spec]
        except KeyError:
            raise ValueError(
                f"unknown admission policy {spec!r}; "
                "have none | token_bucket | queue_depth"
            )
    return AdmissionController(spec, frame_rate)
