"""Segment fast-path: delegate a quiescent co-simulation to the flat kernel.

PR 3 established (property tests over all apps × arrival processes,
including ``timeout="budget"``) that the pipelined event loop and the
vectorized per-module replay (`repro.serving.replay`) agree whenever queues
are unbounded and fanout is deterministic.  This module is that theorem turned into a
cache: when a segment of the run is *quiescent of everything only the
event loop can express* —

* open-loop issue times (no closed-loop clients),
* no admission shedding against live state,
* no control epochs (no machine-set hot-swaps mid-segment),
* every stage unbounded (``queue_cap is None``, no backpressure),
* deterministic accumulator fanout (`fanout.AccumulatorFanout`),
* no adaptive phantom streaming (``phantom_target == 0``)

— the whole segment replays in O(batches) numpy work per machine on the
vectorized kernel (`repro.serving.replay`), filling the same
`result.FrameTable` columns the event loop would have produced, with
finish times BIT-identical to the event cores (the kernel's FIFO chain
evaluates in their operation order).  Every eligibility condition above is
run-constant, so the quiescent segment is always the *entire* run and the
event-loop re-entry point is the end of stream.

**The causal order.**  One construct needs care in the flat replay: the
end-of-stream tail flush with ``timeout=None`` closes a partial batch at
its last member's ready time — *backdating* service into the past, because
a module-by-module replay knows that the stream has ended.  The
event loop only learns that once everything else has drained, so its tail
flushes (and their downstream cascades) happen strictly after all normal
events, round by round.  The fast path tracks a *quiescence depth* per
frame (0 = normal, r = produced in/fed by the r-th tail-flush round) and
orders every module's arrival stream by ``(depth, ready, frame id)``
(`replay.causal_order`) — exactly the event loop's delivery order, even
when a backdated tail on one branch of a join carries an earlier time
than a sibling's normal completions.  The flat kernel itself is causal
(`repro.serving.replay` handles non-monotone ready within a causal
stream), so the fast path never needs to bail to the event loop.

Speed: ~20-40x over the event-by-event loop at 10^4-10^6 frames on the
suite apps (see ``benchmarks.run --only pipeline_speed``), which is what
makes control-plane and SLO sweeps at the ROADMAP's million-frame scale
tractable.
"""
from __future__ import annotations

from typing import Mapping

import numpy as np

from ...core.dag import AppDAG
from ...core.dispatch import dispatch_runs
from ..replay import (
    causal_order,
    fanout_counts,
    lexmax_fold,
    lexmax_parents,
    propagate_depth,
    replay_module,
    runs_to_assignment,
)
from .fanout import AccumulatorFanout
from .result import FrameTable, PipelineResult
from .stages import ModuleStage


def eligible(dag: AppDAG, stages: Mapping[str, ModuleStage]) -> bool:
    """Stage-side fast-path eligibility (caller already checked that the
    run is open-loop with no admission and no control plane).

    A non-analytic service-time source (trace samples, live executor
    timing) is stateful per batch start, so those runs stay on the event
    loop; an analytic source is the profiled constant the kernel already
    uses."""
    return all(
        st.queue_cap is None
        and st.phantom_target <= 0.0
        and isinstance(st.fanout, AccumulatorFanout)
        and getattr(st.service_time, "kind", "analytic") == "analytic"
        for st in stages.values()
    )


def run_flat_segment(
    dag: AppDAG,
    stages: Mapping[str, ModuleStage],
    n_frames: int,
    issue: np.ndarray,
    tail: str,
    obs=None,
) -> PipelineResult:
    """Replay one quiescent segment (the whole eligible run) vectorized.

    Module-by-module in topological order — the replay kernel's schedule,
    which the PR-3 ordering argument showed delivers every frame to every
    stage at the same instant and in the same arrival order as the global
    event loop (streams in causal ``(depth, ready, id)`` order; see module
    docstring).  Per-frame records land in the same `FrameTable` columns
    the event loop fills, so the returned `PipelineResult` is
    indistinguishable from the general path's.

    ``obs`` (an `observability.Observability`) receives *column-level*
    metrics only — per-module batch counts, occupancy, exact busy time
    from the per-machine batch tallies, and the members' collection /
    queueing / service sums from the replay's per-request columns — never
    per-event trace spans:
    keeping the fast path allocation-free per event is what holds sampled
    tracing inside the CI overhead gate.
    """
    topo = dag.topo_order()
    torder = {m: i for i, m in enumerate(topo)}
    parents = {m: sorted(dag.parents(m), key=torder.__getitem__) for m in topo}
    children = {m: sorted(dag.children(m), key=torder.__getitem__) for m in topo}
    sinks = [m for m in topo if not children[m]]
    ancestors = dag.ancestor_closure()

    ft = FrameTable(n_frames, topo, parents, len(sinks))
    ft.issue[:] = issue
    # ``bad[m][f]``: frame f produced no completion at m — voided by a bad
    # parent, skipped by a zero instance count, or every instance dropped
    # (the event loop's stage_resolved(done=False) propagation, columnar)
    bad = {m: np.zeros(n_frames, dtype=bool) for m in topo}
    # quiescence depth of f's completion at m: 0 = produced by the normal
    # event phase, r >= 1 = produced in (the cascade of) the r-th
    # quiescence flush round — the event loop flushes every
    # ancestors-drained stage per round, so round r's completions (and
    # their fill-cascades) all causally precede round r+1's
    depth = {m: np.zeros(n_frames, dtype=np.int64) for m in topo}
    # the processing instant of f's resolve at m — equal to the finish value
    # in the normal phase, but a cascade resolve can be backdated below a
    # sibling branch's finish while still processing after it (the join's
    # delivery order key, alongside depth; see `replay.causal_order`)
    emit = {m: np.zeros(n_frames) for m in topo}
    # the round in which m's own backdated tail (timeout None, flushed
    # partial) fires: one past the last round an ancestor still held work
    tail_round: dict[str, int] = {}

    for m in topo:
        st = stages[m]
        if parents[m]:
            pf = np.stack([ft.finish[p] for p in parents[m]])
            voided = np.isnan(pf).any(axis=0)
            ready = pf.max(axis=0)  # NaN only where voided (excluded below)
            in_depth, in_emit = lexmax_parents(
                [depth[p] for p in parents[m]],
                [emit[p] for p in parents[m]],
            )
        else:
            voided = np.zeros(n_frames, dtype=bool)
            ready = ft.issue
            in_depth = np.zeros(n_frames, dtype=np.int64)
            in_emit = ft.issue
        bad[m] |= voided
        # stage arrival order: causal — (quiescence depth, emit, frame id),
        # the order the event loop's (t, seq) heap + (topo, frame)
        # same-instant delivery + after-drain tail rounds realize
        order = causal_order(ready, in_depth, in_emit)
        alive = order[~voided[order]]
        counts = fanout_counts(alive.size, st.fanout.phi)
        ft.fan[m][alive] = counts
        taken = counts > 0
        entered = alive[taken]
        ft.avail[m][entered] = ready[entered]
        bad[m][alive[~taken]] = True  # zero-fanout skip: vacuously resolved

        instances = np.repeat(alive, counts)
        if instances.size == 0:
            tail_round[m] = 0
            continue
        ready_inst = ready[instances]
        machines = st.machines
        timeout = {mm.mid: st.cores[mm.mid].timeout for mm in machines}
        runs = dispatch_runs(machines, instances.size, st.policy)
        rep = replay_module(
            machines, ready_inst, runs, timeout=timeout, tail=tail,
            with_closed=obs is not None,
        )
        done = rep.done
        # per-frame finish = max over the frame's completed instances
        # (partial completion proceeds with the instances that did finish)
        fmax = np.full(n_frames, -np.inf)
        np.maximum.at(fmax, instances[done], rep.finish[done])
        has_done = fmax > -np.inf
        ft.finish[m][has_done] = fmax[has_done]
        had = np.zeros(n_frames, dtype=bool)
        had[entered] = True
        lost_here = had & ~has_done
        ft.lost |= lost_here
        bad[m] |= lost_here

        # propagate quiescence depth through service so downstream joins
        # can re-establish the causal order (`replay.propagate_depth`);
        # each frame's resolve key is the lexicographic (depth, finish)
        # max over its completed instances
        assignment = runs_to_assignment(runs, instances.size)
        out_inst, tail_round[m] = propagate_depth(
            in_depth[instances], assignment, rep.finish, machines, timeout,
            tail,
            max((tail_round.get(a, 0) for a in ancestors[m]), default=0),
        )
        lexmax_fold(
            instances[done], out_inst[done], rep.finish[done],
            depth[m], emit[m],
        )

        ss = st.stats
        ss.batches += rep.n_batches
        ss.dropped += instances.size - int(done.sum())
        ss.latencies.extend((rep.finish[done] - ready_inst[done]).tolist())
        if obs is not None:
            # exact column-level accounting: ModuleReplay tallies executed
            # batches per machine, so busy time and capacity slots come
            # from each machine's own config — no per-event hooks
            by_mid = {mm.mid: mm.config for mm in machines}
            obs.bulk_module(
                m,
                batches=rep.n_batches,
                members=int(done.sum()),
                phantoms=0,
                slots=sum(
                    k * by_mid[mid].batch for mid, k in rep.batches.items()
                ),
                busy=sum(
                    k * by_mid[mid].duration for mid, k in rep.batches.items()
                ),
            )
            obs.waits(m, *rep.waits(ready_inst, machines))

    sink_finish = np.stack([ft.finish[s] for s in sinks])
    ok = ~np.isnan(sink_finish).any(axis=0)
    ft.e2e[ok] = sink_finish.max(axis=0)[ok] - ft.issue[ok]
    ft.resolved[:] = True  # every frame is accounted: done, skipped, or lost
    return ft.finalize(dag, {m: stages[m].stats for m in topo}, attempts=0)
