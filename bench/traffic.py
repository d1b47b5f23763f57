"""The one arrival generator: reads a traffic mix's parameters, makes arrivals.

Every process draws from fixed multisets of sizes that depend only on the
mix and the chunk length, and the seed only orders them.  Two seeds therefore
offer the same amount of work with the same gap distribution, and differ only
in the order of the gaps (a seed that changed the work would widen the
run-to-run spread without measuring anything).

* ``poisson``: exponential inter-arrival gaps at ``rate``, taken as the
  midpoint quantiles of Exp(rate), rescaled so that ``n`` gaps span exactly
  ``n / rate`` seconds, in a seeded order.
* ``mmpp``: the 2-state Markov-modulated Poisson process of
  ``repro.serving.arrivals.mmpp_arrivals`` (copied here so that the program
  cannot change the yardstick): a calm state and a burst state at ``burst``
  times its intensity, ``frac_burst`` of the time in bursts, mean calm+burst
  cycle ``mean_cycle_s``.  Unit-rate gaps and the dwell times of both states
  come from quantile multisets in a seeded order, and the unit-rate process is
  mapped through the inverse integrated intensity.
"""
from __future__ import annotations

import numpy as np

MASK64 = (1 << 64) - 1


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator for ``seed`` (any whole number) and a stream path."""
    return np.random.default_rng([seed & MASK64, *stream])


def _exp_quantiles(n: int) -> np.ndarray:
    """Midpoint quantiles of Exp(1), rescaled to mean exactly 1."""
    q = -np.log1p(-(np.arange(n) + 0.5) / n)
    return q * (n / q.sum())


def poisson(n: int, rate: float, rng: np.random.Generator) -> np.ndarray:
    gaps = rng.permutation(_exp_quantiles(n)) / rate
    return np.cumsum(gaps) - gaps[0]


def mmpp(
    n: int,
    rate: float,
    rng: np.random.Generator,
    *,
    burst: float,
    frac_burst: float,
    mean_cycle_s: float,
) -> np.ndarray:
    if not (burst >= 1.0 and 0.0 < frac_burst < 1.0 and mean_cycle_s > 0):
        raise ValueError("mmpp needs burst >= 1, 0 < frac_burst < 1, mean_cycle_s > 0")
    r0 = rate / (1.0 - frac_burst + frac_burst * burst)
    r1 = burst * r0
    unit = np.cumsum(rng.permutation(_exp_quantiles(n)))
    # enough cycles to cover the chunk twice over; the unused tail is cut
    cycles = max(4, int(np.ceil(2.0 * n / (rate * mean_cycle_s))))
    calm = rng.permutation(_exp_quantiles(cycles)) * mean_cycle_s * (1.0 - frac_burst)
    hot = rng.permutation(_exp_quantiles(cycles)) * mean_cycle_s * frac_burst
    dwell = np.empty(2 * cycles)
    dwell[0::2], dwell[1::2] = calm, hot
    lam = np.empty(2 * cycles)
    lam[0::2], lam[1::2] = r0 * calm, r1 * hot
    knots_t = np.concatenate([[0.0], np.cumsum(dwell)])
    knots_lam = np.concatenate([[0.0], np.cumsum(lam)])
    if knots_lam[-1] < unit[-1]:
        raise ValueError("mmpp: dwell cycles do not cover the chunk")
    t = np.interp(unit, knots_lam, knots_t)
    return t - t[0]


def arrivals(mix: dict, n: int, seed: int, chunk: int, scale: float = 1.0) -> np.ndarray:
    """``n`` sorted arrival times (seconds from 0) for chunk ``chunk`` of a run.

    ``scale`` multiplies the offered rate (the offered-rate sweep); the cells
    run at 1.0, where the offered rate is the provisioned one.
    """
    rng = rng_for(seed, chunk)
    rate = float(mix["rate"]) * scale
    if mix["process"] == "poisson":
        return poisson(n, rate, rng)
    if mix["process"] == "mmpp":
        return mmpp(
            n, rate, rng,
            burst=float(mix["burst"]),
            frac_burst=float(mix["frac_burst"]),
            mean_cycle_s=float(mix["mean_cycle_s"]),
        )
    raise ValueError(f"unknown arrival process {mix['process']!r}")
