"""Event-driven core vs vectorized replay kernel: they must agree exactly.

Also the tail-batch regression suite: the seed engine "flushed" tail batches
with a no-op deadline (`t_ready = max(t_ready, t_ready)`) and the seed
simulator dropped them outright; the unified core gives partial batches real
deadline semantics (flush when the opener has waited ``timeout``), mid-stream
and at end of stream.
"""
import random

import numpy as np
import pytest

from repro.core.dispatch import Machine, Policy, dispatch_runs
from repro.core.profiles import Config
from repro.serving import simulate, simulate_reference
from repro.serving.arrivals import make_arrivals
from repro.serving.events import simulate_module_events
from repro.serving.replay import replay_machine, replay_module, runs_to_assignment


def _random_machines(rng: random.Random) -> list[Machine]:
    machines = []
    for mid in range(rng.randint(1, 4)):
        b = 2 ** rng.randint(0, 5)
        d = round(rng.uniform(0.02, 0.4), 6)
        cfg = Config(b, d, "hw", rng.choice([1.0, 1.35, 1.75]))
        rate = cfg.throughput * rng.uniform(0.3, 1.0)
        machines.append(Machine(mid, cfg, rate))
    return machines


@pytest.mark.parametrize("policy", [Policy.TC, Policy.RR])
@pytest.mark.parametrize("kind", ["uniform", "poisson", "mmpp"])
def test_vectorized_matches_event_core(policy, kind):
    rng = random.Random(hash((policy.value, kind)) & 0xFFFF)
    for trial in range(8):
        machines = _random_machines(rng)
        n = rng.randint(30, 400)
        rate = sum(m.rate for m in machines)
        ready = make_arrivals(kind, n, rate, seed=trial)
        runs = dispatch_runs(machines, n, policy)
        timeout = rng.choice([None, 0.05, 0.5, 5.0])
        tail = rng.choice(["flush", "drop"]) if timeout is None else "flush"
        vec = replay_module(machines, ready, runs, timeout=timeout, tail=tail)
        ev = replay_module(
            machines, ready, runs, timeout=timeout, tail=tail, method="events"
        )
        np.testing.assert_array_equal(vec.assignment, ev.assignment)
        assert vec.batches == ev.batches, (trial, timeout, tail)
        np.testing.assert_allclose(
            vec.finish, ev.finish, rtol=0, atol=1e-9, equal_nan=True
        )


def test_per_machine_timeout_mapping():
    """`timeout` may be a per-machine-id mapping (shorter collection windows
    for slower machines); kernel and event core must agree on it."""
    rng = random.Random(99)
    for trial in range(6):
        machines = _random_machines(rng)
        n = rng.randint(50, 300)
        rate = sum(m.rate for m in machines)
        ready = make_arrivals("mmpp", n, rate, seed=trial)
        runs = dispatch_runs(machines, n, Policy.TC)
        wmap = {m.mid: rng.uniform(0.05, 1.0) for m in machines}
        vec = replay_module(machines, ready, runs, timeout=wmap)
        ev = replay_module(machines, ready, runs, timeout=wmap, method="events")
        assert vec.batches == ev.batches
        np.testing.assert_allclose(
            vec.finish, ev.finish, rtol=0, atol=1e-9, equal_nan=True
        )


def test_simulate_events_method_agrees():
    cfg = Config(8, 0.1)
    machines_rate = 8 / 0.1
    from repro.core.dispatch import Alloc

    allocs = [Alloc(cfg, machines=2.0, rate=2 * machines_rate)]
    for kind in ("uniform", "poisson"):
        a = simulate(allocs, 2 * machines_rate, n_requests=500, arrivals=kind)
        b = simulate(
            allocs, 2 * machines_rate, n_requests=500, arrivals=kind, method="events"
        )
        assert a.n_requests == b.n_requests
        assert a.max_latency == pytest.approx(b.max_latency, abs=1e-9)
        assert a.mean_latency == pytest.approx(b.mean_latency, abs=1e-9)


# ---------------------------------------------------------------- tail batches


def test_tail_requests_complete_under_timeout():
    """Regression (seed bug): tail requests now complete with real deadline
    semantics instead of inheriting whole-batch / drop behavior."""
    cfg = Config(8, 0.1)
    m = Machine(0, cfg, cfg.throughput)
    rate = cfg.throughput
    n = 20  # 2 full batches of 8 + a tail of 4
    ready = make_arrivals("uniform", n, rate)
    w = 0.3
    finish, _ = replay_machine(ready, 8, 0.1, timeout=w)
    assert not np.isnan(finish).any(), "tail requests must complete"
    # the tail batch opens at request 16 and flushes exactly at opener + W
    expected_flush = ready[16] + w
    assert finish[16:] == pytest.approx(expected_flush + 0.1)
    # legacy simulator dropped exactly those 4 requests
    from repro.core.dispatch import Alloc

    ref = simulate_reference([Alloc(cfg, 1.0, rate)], rate, n_requests=n)
    assert ref.n_requests == 16
    new = simulate([Alloc(cfg, 1.0, rate)], rate, n_requests=n, timeout=w, tail="flush")
    assert new.n_requests == n and new.dropped == 0


def test_no_op_deadline_fixed_tail_latency_bounded():
    """With a finite timeout, a tail request's latency is bounded by
    timeout + service (+ queueing), not by the never-arriving batch fill."""
    cfg = Config(32, 0.05)  # big batch: without the deadline the tail waits on
    rate = 100.0            # 24 more requests that never come
    ready = make_arrivals("uniform", 8, rate)  # lone partial batch
    w = 0.2
    finish, nb = replay_machine(ready, 32, 0.05, timeout=w)
    assert nb == 1
    lat = finish - ready
    assert lat.max() <= w + 0.05 + 1e-9
    # and the flush happens at the deadline, not at the last arrival
    assert finish[0] == pytest.approx(ready[0] + w + 0.05)


def test_midstream_timeout_flush_on_burst_gap():
    """A long arrival gap triggers a mid-stream partial flush — the event
    core and the kernel's greedy fallback must both split the batch."""
    ready = np.array([0.0, 0.01, 0.02, 0.03, 5.0, 5.01, 5.02, 5.03])
    cfg = Config(8, 0.1)
    m = Machine(0, cfg, cfg.throughput)
    for impl in ("kernel", "events"):
        if impl == "kernel":
            finish, nb = replay_machine(ready, 8, 0.1, timeout=1.0)
        else:
            finish, batches = simulate_module_events(
                [m], ready, np.zeros(8, dtype=int), timeout=1.0
            )
            nb = batches[0]
        assert nb == 2, impl
        # first four flush at t=0+1.0, done at 1.1; second four at 5.0+1.0
        assert finish[:4] == pytest.approx(1.1), impl
        assert finish[4:] == pytest.approx(6.1), impl


def test_tail_drop_vs_flush_without_timeout():
    ready = make_arrivals("uniform", 10, 50.0)
    f_drop, nb_drop = replay_machine(ready, 8, 0.1, tail="drop")
    f_flush, nb_flush = replay_machine(ready, 8, 0.1, tail="flush")
    assert np.isnan(f_drop[8:]).all() and not np.isnan(f_drop[:8]).any()
    assert not np.isnan(f_flush).any()
    assert nb_drop == 1 and nb_flush == 2
    # seed-engine semantics: tail executes at its last arrival
    assert f_flush[8:] == pytest.approx(max(ready[9], f_flush[0]) + 0.1)
