"""Frozen legacy pure-Python replay loops (the seed implementations).

Kept word-for-word as golden references: the event-driven core and the
vectorized replay kernel must reproduce these numbers on uniform arrivals
(tests/test_golden_equivalence.py), and the benchmark suite measures the
vectorized speedup against them (`benchmarks.run --only replay`).

Seed quirks are preserved on purpose — do NOT fix or optimize them here:

* ``simulate_reference`` drops incomplete tail batches outright (steady-state
  accounting only);
* ``engine_run_reference`` "flushes" tail batches with the no-op deadline
  ``t_ready = max(t_ready, t_ready)`` — i.e. executes them the moment their
  last request arrives, with no real timeout semantics.

The maintained semantics live in `repro.serving.events` and
`repro.serving.replay`.
"""
from __future__ import annotations

from ..core.dispatch import Alloc, Machine, Policy, expand_machines
from ..core.harpagon import Plan
from .engine import ModuleStats, ServeResult
from .simulator import SimResult


def dispatch_trace_reference(
    machines: list[Machine], n_requests: int, policy: Policy
) -> list[tuple[int, int]]:
    """The seed `core.dispatch.dispatch_trace` loop, verbatim.

    The live `dispatch_runs` is a vectorized merge-sort of the same periodic
    run slots; it can legitimately differ from this greedy walk on float
    near-ties (accumulated ``next_t += p`` vs ``k * p``).  Keeping the seed
    loop frozen here means the golden tests pin the *whole* seed pipeline,
    dispatcher included, rather than comparing the new dispatcher to itself.
    """
    out: list[tuple[int, int]] = []
    if policy is Policy.TC:
        next_t = [0.0] * len(machines)
        rid = 0
        while rid < n_requests:
            j = min(
                range(len(machines)),
                key=lambda i: (next_t[i], -machines[i].config.ratio, i),
            )
            m = machines[j]
            take = min(m.config.batch, n_requests - rid)
            for _ in range(take):
                out.append((rid, m.mid))
                rid += 1
            next_t[j] += m.config.batch / m.rate
        return out
    credit = [0.0] * len(machines)
    tot = sum(m.rate for m in machines)
    for rid in range(n_requests):
        for i, m in enumerate(machines):
            credit[i] += m.rate / tot
        j = max(range(len(machines)), key=lambda i: credit[i])
        credit[j] -= 1.0
        out.append((rid, machines[j].mid))
    return out


def simulate_reference(
    allocs: list[Alloc],
    total_rate: float,
    *,
    policy: Policy = Policy.TC,
    n_requests: int = 2000,
) -> SimResult:
    """The seed `serving.simulator.simulate` loop, verbatim."""
    machines = expand_machines(allocs)
    trace = dispatch_trace_reference(machines, n_requests, policy)
    arrivals = [i / total_rate for i in range(n_requests)]

    by_machine: dict[int, list[int]] = {m.mid: [] for m in machines}
    for rid, mid in trace:
        by_machine[mid].append(rid)

    latency = [0.0] * n_requests
    per_machine_max: dict[int, float] = {}
    for m in machines:
        rids = by_machine[m.mid]
        b, d = m.config.batch, m.config.duration
        free_at = 0.0
        worst = 0.0
        for i in range(0, len(rids), b):
            group = rids[i : i + b]
            if len(group) < b:
                break  # incomplete tail batch: not in steady state, drop
            ready = arrivals[group[-1]]
            start = max(ready, free_at)
            finish = start + d
            free_at = finish
            for rid in group:
                lat = finish - arrivals[rid]
                latency[rid] = lat
                worst = max(worst, lat)
        per_machine_max[m.mid] = worst
    done = [l for l in latency if l > 0]
    return SimResult(
        max_latency=max(done) if done else 0.0,
        mean_latency=sum(done) / len(done) if done else 0.0,
        per_machine_max=per_machine_max,
        n_requests=len(done),
    )


def engine_run_reference(
    plan: Plan, n_frames: int, frame_rate: float, *, policy: Policy = Policy.TC
) -> ServeResult:
    """The seed `serving.engine.ServingEngine.run` virtual-time loop, verbatim
    (minus the real-executor branch, which the seed example alone used)."""
    wl = plan.workload
    arrival = [i / frame_rate for i in range(n_frames)]
    finish_at = {m: [0.0] * n_frames for m in wl.app.modules}
    stats = {m: ModuleStats() for m in wl.app.modules}

    def _topo():
        seen: list[str] = []
        mods = list(wl.app.modules)
        while mods:
            for m in mods:
                if all(p in seen for p in wl.app.parents(m)):
                    seen.append(m)
                    mods.remove(m)
                    break
            else:
                raise RuntimeError("cycle in DAG")
        return seen

    def replay_one(m, ready, drop, fanout, finish, st: ModuleStats):
        sched = plan.schedules[m]
        machines = expand_machines(list(sched.allocs))
        order = sorted(range(n_frames), key=lambda i: ready[i])
        instances: list[int] = []
        acc = 0.0
        for i in order:
            if drop[i]:
                continue
            acc += fanout
            k = int(acc)
            acc -= k
            instances.extend([i] * k)
        n = len(instances)
        if n == 0:
            return
        trace = dispatch_trace_reference(machines, n, policy)
        by_machine: dict[int, list[int]] = {mm.mid: [] for mm in machines}
        for slot, mid in trace:
            by_machine[mid].append(instances[slot])
        for mm in machines:
            fids = by_machine[mm.mid]
            b, d = mm.config.batch, mm.config.duration
            free = 0.0
            for i in range(0, len(fids), b):
                group = fids[i : i + b]
                t_ready = max(ready[f] for f in group)
                if len(group) < b:
                    # tail batch: flushed on deadline (early-exec semantics)
                    t_ready = max(t_ready, t_ready)
                start = max(t_ready, free)
                end = start + d
                free = end
                st.batches += 1
                for f in group:
                    finish[f] = max(finish[f], end)
                    st.latencies.append(end - ready[f])

    for m in _topo():
        parents = wl.app.parents(m)
        ready = [
            max([arrival[i]] + [finish_at[p][i] for p in parents])
            for i in range(n_frames)
        ]
        drop = [
            any(finish_at[p][i] <= 0.0 for p in parents) for i in range(n_frames)
        ] if parents else [False] * n_frames
        fanout = wl.rates[m] / frame_rate
        replay_one(m, ready, drop, fanout, finish_at[m], stats[m])
    sinks = [m for m in wl.app.modules if not wl.app.children(m)]
    e2e = [
        max(finish_at[s][i] for s in sinks) - arrival[i]
        for i in range(n_frames)
        if all(finish_at[s][i] > 0 for s in sinks)
    ]
    return ServeResult(e2e, stats, wl.slo)
