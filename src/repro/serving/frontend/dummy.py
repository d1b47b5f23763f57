"""Dummy-request injection: stream the plan's priced phantom traffic.

The scheduler prices dummy traffic two ways (`core.residual.apply_dummy`,
Theorem 2 padding, and `core.scheduler._dummy_fill`, the residual machine),
but a plan's ``Alloc.dummy`` / ``ModuleSchedule.dummy`` rates only matter at
serving time if the frontend actually *streams* them: phantom requests join
batch formation so batches collect at the provisioned rate — that is what
makes the modeled WCL (``d + b/w`` with ``w`` including dummy rate)
achievable — then their slots are excluded from every latency/attainment
statistic.

The injector is adaptive: it pads the module's observed real request rate up
to the plan's total collection rate, so driving a module *above* its
provisioned rate injects proportionally fewer (eventually zero) phantoms,
exactly like a real frontend that only fills otherwise-idle batch slots.
"""
from __future__ import annotations

import math

import numpy as np


def phantom_times(ready: np.ndarray, target_rate: float) -> np.ndarray:
    """Phantom arrival times padding ``ready`` up to ``target_rate`` req/s.

    ``ready`` is the module's sorted real request stream.  Phantoms are paced
    evenly at the deficit rate ``target_rate - observed_rate`` over the real
    stream's span (the frontend generates them, so it can pace perfectly),
    phase-offset by half a period so they interleave with real traffic.
    Returns an empty array when the real stream already meets the target.
    """
    n = ready.size
    if n < 2 or target_rate <= 0.0:
        return np.zeros(0)
    t0, t1 = float(ready[0]), float(ready[-1])
    span = t1 - t0
    if span <= 0.0:
        return np.zeros(0)
    observed = (n - 1) / span
    pad = target_rate - observed
    if pad <= 1e-9:
        return np.zeros(0)
    k = int(math.floor(pad * span))
    if k <= 0:
        return np.zeros(0)
    return t0 + (np.arange(k, dtype=np.float64) + 0.5) / pad
