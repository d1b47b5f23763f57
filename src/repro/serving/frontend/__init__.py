"""Overload-aware serving frontend: the layer between arrivals and dispatch.

The PR-1 simulator modeled a serving cluster that can only be driven *at*
its provisioned rate: dummy traffic was priced but never streamed, every
arrival was admitted no matter the backlog, and clients were open-loop.
This package adds the three frontend behaviors real inference clouds hinge
on, all opt-in via :class:`FrontendConfig` (the default reproduces PR-1 /
seed numbers exactly):

* **dummy streaming** (`.dummy`) — the plan's priced ``Alloc.dummy`` traffic
  is injected as phantom requests into batch formation, so dummy-padded
  plans hit their modeled WCL and ``timeout="budget"`` no longer needs a
  fill-time floor; phantom slots count toward batch fill but never toward
  latency/attainment statistics.
* **admission control** (`.admission`) — token-bucket or queue-depth
  shedding at ingress (per-app policies supported) bounds p99 under bursty
  overload at the price of an explicit, reported shed rate.
* **closed-loop clients** (`.clients`) — bounded in-flight frames per
  client with optional jittered retry-on-shed, issued by the pipelined
  event loop as frames actually resolve.

Under the incremental control plane (``repro.serving.control``) the
frontend re-reads *per-epoch plan state* instead of run constants: an
admission policy bound to the provisioned rate follows each hot-swapped
plan (`AdmissionController.rebind`), and clients with ``backoff=None``
wait about one *live* modeled service round between shed retries.

Usage sketch::

    from repro.serving import ServingEngine
    from repro.serving.frontend import FrontendConfig, TokenBucket

    fe = FrontendConfig(dummies=True, admission=TokenBucket(burst=4))
    res = ServingEngine(plan).run(
        2000, frame_rate, arrivals="mmpp", timeout="budget", frontend=fe,
        offered_rate=1.3 * frame_rate,   # drive past provisioning
    )
    res.attainment, res.shed, res.p99    # shed frames count as SLO misses
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Mapping

from .admission import (
    AdmissionController,
    AdmissionPolicy,
    QueueDepth,
    TokenBucket,
    make_admission,
)
from .clients import ClosedLoopClients
from .dummy import phantom_times


@dataclass(frozen=True)
class FrontendConfig:
    """Frontend behavior knobs for one `ServingEngine.run`.

    The default instance is the identity frontend: no dummy streaming, admit
    everything, open-loop arrivals — bit-identical to running without one.

    ``burst_deadline`` (opt-in, meaningful with ``dummies=True`` and
    ``timeout="budget"``) extends each machine's flush deadline by one
    upstream batch-arrival quantum (`repro.serving.engine.plan_burst`) —
    the deadline-side mirror of the burst-aware WCL correction, closing the
    PR-4 finding where zero-slack deadlines downstream of batched stages
    flush partial batches on every straddled inter-completion gap and
    attainment collapses below 0.5 at 1.0x provisioning.
    """

    dummies: bool = False
    admission: "AdmissionPolicy | Mapping[str, AdmissionPolicy]" = None
    clients: ClosedLoopClients | None = None
    burst_deadline: bool = False


__all__ = [
    "AdmissionController",
    "AdmissionPolicy",
    "ClosedLoopClients",
    "FrontendConfig",
    "QueueDepth",
    "TokenBucket",
    "make_admission",
    "phantom_times",
]
