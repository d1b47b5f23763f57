"""Per-frame fanout realization: deterministic accumulator or correlated draws.

The replay kernel scales every module's instance stream by the fixed ratio
``rates[m] / frame_rate`` through a fractional accumulator
(`repro.serving.replay.expand_fanout`): frame *i*'s instance count at module
*m* depends only on its position in the module's ready order.  Real video
pipelines are not that regular — a busy detector frame yields many crops,
and it yields them for *every* downstream classifier at once (the
cross-sibling load correlation OCTOPINF and Edge-Assisted DNN Serving
measure dominating tail latency).  :class:`FanoutSpec` selects the regime:

* ``"deterministic"`` (default) — the accumulator, instance-stream-identical
  to the replay kernel, so the pipelined co-simulation cross-validates
  against the vectorized kernel bit-for-bit.
* ``"stochastic"`` — per-frame counts ``Poisson(phi_m * B[f, m])`` where the
  *busyness factor* mixes one mean-1 Gamma draw shared by the whole frame
  with an idiosyncratic per-module draw::

      B[f, m] = rho * G[f] + (1 - rho) * H[f, m]

  ``rho = correlation`` steers sibling coupling (1.0: a crowded frame loads
  every classifier at once; 0.0: independent module jitter) and ``cv`` is
  the busyness coefficient of variation.  Counts at *source* modules clamp
  to >= 1 — a frame must physically exist to enter the DAG.  All draws are
  seeded and drawn up front, so counts are position-independent and
  reproducible regardless of event interleaving.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Iterable, Mapping

import numpy as np


@dataclass(frozen=True)
class FanoutSpec:
    """How many module-level instances one frame spawns at each module."""

    mode: str = "deterministic"  # "deterministic" | "stochastic"
    cv: float = 0.5              # busyness coefficient of variation
    correlation: float = 1.0     # share of busyness common to the whole frame

    def __post_init__(self):
        if self.mode not in ("deterministic", "stochastic"):
            raise ValueError(f"unknown fanout mode {self.mode!r}")
        if self.cv < 0.0:
            raise ValueError("cv must be >= 0")
        if not 0.0 <= self.correlation <= 1.0:
            raise ValueError("correlation must lie in [0, 1]")


class AccumulatorFanout:
    """Stateful accumulator: the k-th frame *arriving at the stage* spawns
    ``floor(k * phi) - floor((k - 1) * phi)`` instances — exactly
    `expand_fanout`'s per-position semantics (including its exact-binary
    fast path for half-integer fanouts), so the pipelined co-simulation
    reproduces the replay kernel's instance streams."""

    def __init__(self, phi: float):
        self.phi = float(phi)
        self._exact = float(2.0 * phi).is_integer()
        self._k = 0
        self._acc = 0.0

    def count(self, frame: int) -> int:
        self._k += 1
        if self._exact:
            return int(
                math.floor(self.phi * self._k) - math.floor(self.phi * (self._k - 1))
            )
        self._acc += self.phi
        c = int(self._acc)
        self._acc -= c
        return c


class DrawnFanout:
    """Pre-drawn per-frame counts (stochastic mode): position-independent."""

    def __init__(self, counts: np.ndarray):
        self.counts = np.asarray(counts, dtype=np.int64)

    def count(self, frame: int) -> int:
        return int(self.counts[frame])


def draw_counts(
    spec: FanoutSpec,
    n_frames: int,
    fanouts: Mapping[str, float],
    sources: Iterable[str],
    seed: int = 0,
) -> dict[str, np.ndarray]:
    """Draw correlated per-frame instance counts for every module.

    One shared busyness draw per frame plus one idiosyncratic draw per
    (frame, module), mixed by ``spec.correlation``; module means stay at
    ``fanouts[m]`` (sources slightly above, from the >= 1 clamp).
    """
    rng = np.random.default_rng(seed)
    modules = list(fanouts)
    if spec.cv <= 0.0:
        shared = np.ones(n_frames)
        own = np.ones((n_frames, len(modules)))
    else:
        k = 1.0 / (spec.cv * spec.cv)
        shared = rng.gamma(k, 1.0 / k, size=n_frames)
        own = rng.gamma(k, 1.0 / k, size=(n_frames, len(modules)))
    rho = spec.correlation
    src = set(sources)
    out: dict[str, np.ndarray] = {}
    for j, m in enumerate(modules):
        busy = rho * shared + (1.0 - rho) * own[:, j]
        counts = rng.poisson(fanouts[m] * busy).astype(np.int64)
        if m in src:
            counts = np.maximum(counts, 1)
        out[m] = counts
    return out


def make_stage_fanouts(
    spec: FanoutSpec,
    fanouts: Mapping[str, float],
    sources: Iterable[str],
    n_frames: int,
    seed: int = 0,
) -> dict[str, "AccumulatorFanout | DrawnFanout"]:
    """Resolve one per-stage fanout realizer for every module."""
    if spec.mode == "deterministic":
        return {m: AccumulatorFanout(phi) for m, phi in fanouts.items()}
    counts = draw_counts(spec, n_frames, fanouts, sources, seed)
    return {m: DrawnFanout(counts[m]) for m in fanouts}
