"""The arrival generator: seeded, same work for every seed, exact mean rate."""
import json

import numpy as np
import pytest

from bench import traffic
from bench.tests.tiny import REPO

MIXES = {p.stem: json.loads(p.read_text()) for p in (REPO / "bench" / "traffic").glob("*.json")}
BIG = 2**31 + 12345  # seeds are larger than 32 signed bits hold


@pytest.mark.parametrize("name", sorted(MIXES) + ["mmpp"])
def test_same_seed_same_arrivals(name):
    mix = MMPP if name == "mmpp" else MIXES[name]
    a = traffic.arrivals(mix, 500, BIG, 3)
    assert np.array_equal(a, traffic.arrivals(mix, 500, BIG, 3))
    assert not np.array_equal(a, traffic.arrivals(mix, 500, BIG, 4))
    assert a[0] == 0.0 and np.all(np.diff(a) >= 0)


def test_poisson_gaps_are_one_multiset_in_seeded_orders():
    mix = MIXES["poisson-300rps-slo1000ms"]
    g1 = np.diff(traffic.arrivals(mix, 400, 1, 0))
    g2 = np.diff(traffic.arrivals(mix, 400, BIG, 7))
    assert not np.array_equal(g1, g2)
    full = traffic._exp_quantiles(400) / 300.0
    assert np.isclose(full.sum(), 400 / 300.0)
    # every gap of either seed is one of the set's sizes, each used once
    for g in (g1, g2):
        idx = np.abs(g[:, None] - full[None, :]).argmin(1)
        assert np.allclose(full[idx], g, rtol=0, atol=1e-12)
        assert len(set(idx)) >= len(g) - 1


# the bursty mix measured for chain-tight-bursty (kept out of the cells; PERF.md)
MMPP = {"process": "mmpp", "rate": 300.0, "burst": 4.0, "frac_burst": 0.2, "mean_cycle_s": 0.5}


def test_mmpp_keeps_its_mean_rate_and_bursts():
    mix = MMPP
    rates = []
    for seed in range(8):
        t = traffic.arrivals(mix, 3000, seed, 0)
        rates.append(3000 / t[-1])
    assert 0.8 * 300 < np.mean(rates) < 1.2 * 300
    t = traffic.arrivals(mix, 3000, 5, 0)
    per_50ms = np.histogram(t, bins=np.arange(0, t[-1], 0.05))[0]
    assert per_50ms.max() > 2.0 * per_50ms.mean()


def test_scale_multiplies_the_offered_rate():
    mix = MIXES["poisson-1000rps-slo10ms"]
    a = traffic.arrivals(mix, 1000, 9, 0)
    b = traffic.arrivals(mix, 1000, 9, 0, scale=1.25)
    assert np.allclose(a, 1.25 * b)


def test_unknown_process_is_an_error():
    with pytest.raises(ValueError):
        traffic.arrivals({"process": "zipf", "rate": 1.0}, 10, 0, 0)
