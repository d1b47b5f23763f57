"""Multi-module pipelined co-simulation: one event loop over the whole DAG.

A per-module replay (`repro.serving.replay`) runs modules one at a time in
topological order: each module's full request stream is known before the
module runs, and downstream only sees per-frame *finish times*.  That is
exact while queues are unbounded and fanout is deterministic — and blind to
everything else.  This core instead pushes each frame through the app DAG as
a tracked entity inside one global discrete-event loop:

* per-module **ingress is fed by upstream batch completions** (not by an
  independent arrival process): a detector batch finishing at ``t`` lands
  its frames' classifier crops at ``t``, in frame order;
* **bounded queues exert backpressure**: a stage at ``queue_cap`` parks
  deliveries FIFO and the upstream machine that produced them *stays busy*
  until the stage drains — upstream throughput degrades exactly like a real
  pipeline with finite inter-stage buffers;
* **fanout is per-frame** (`.fanout.FanoutSpec`): deterministic accumulator
  (the replay kernel's `expand_fanout`) or seeded stochastic draws
  correlated across sibling modules;
* **clients and admission live inside the loop**: closed-loop slots issue
  the next frame when the previous one actually resolves, and queue-depth
  admission sheds against the true number of frames in flight — no
  fixed-point iteration, no latency oracle from a previous pass.

Event ordering at equal timestamps mirrors the single-module reference core:
arrivals join batches at their deadline instant, and upstream machine-frees
deliver before a downstream flush at the same instant fires (see
`stages._K_*`).  All same-time machine-frees are collected before their
outputs are delivered, sorted by ``(stage topo index, frame id)`` — the same
order a stable ready-sort produces, which is what makes the co-simulation
cross-validate bit-for-bit against the vectorized kernel on unbounded queues
with deterministic fanout.

**Macro-event hot path.**  The event-by-event loop is the semantics oracle,
not the speed target.  Three layers sit on top of it:

* per-frame state lives in preallocated struct-of-arrays columns
  (`result.FrameTable`) indexed by frame id — no per-frame dicts;
* same-instant work is drained in macro-events: all machine-frees at one
  timestamp deliver together (pre-existing), and a frame's whole fanout
  enters a stage through one `ModuleStage.deliver_run` walk advance instead
  of per-instance dispatcher calls;
* when the run is **quiescent of everything only the event loop can
  express** — open-loop issue, unbounded queues, deterministic fanout, no
  phantom streaming, no admission, no control epochs — the entire segment
  (here: the whole run) is delegated to the vectorized replay kernel
  (`.fastpath`), a cache of the PR-3 equivalence theorem.  The event loop
  would be re-entered at the segment boundary; with run-constant
  eligibility there is exactly one segment.

``PipelineConfig(reference=True)`` pins the original event-by-event loop
(scalar delivery, no fast path) as the bit-exactness oracle.  Both loops
pop one global ``heapq`` in ``(t, kind, seq)`` order.
"""
from __future__ import annotations

import heapq
import math
from dataclasses import dataclass
from typing import Mapping

import numpy as np

from ...core.dag import AppDAG
from ..frontend.admission import AdmissionController
from ..frontend.clients import ClosedLoopClients
from .fanout import FanoutSpec
from .result import FrameTable, PipelineResult
from .stages import (
    Instance, ModuleStage, _K_ARRIVE, _K_EPOCH, _K_FAULT, _K_FLUSH, _K_FREE,
)


@dataclass(frozen=True)
class PipelineConfig:
    """Engine-facing knobs for ``ServingEngine.run(pipeline=...)``.

    ``queue_cap`` bounds every stage's ingress backlog of real instances
    waiting to start service; ``None`` disables backpressure (unbounded
    queues).  ``fanout`` selects deterministic or correlated-stochastic
    per-frame fanout.

    Performance knobs (results are invariant to both):

    * ``reference`` — run the original event-by-event loop (scalar
      per-instance delivery, no segment fast-path): the bit-exactness
      oracle the macro-event path is property-tested against.
    * ``fast_path`` — allow delegating a control-quiescent run to the
      vectorized replay kernel (`repro.serving.pipeline.fastpath`); setting
      it ``False`` keeps the macro-event general loop even when eligible
      (useful for benchmarking the loop itself).
    """

    fanout: FanoutSpec = FanoutSpec()
    queue_cap: "int | None" = None
    reference: bool = False
    fast_path: bool = True


def run_pipeline(
    dag: AppDAG,
    stages: Mapping[str, ModuleStage],
    n_frames: int,
    *,
    issue: "np.ndarray | None" = None,
    clients: "ClosedLoopClients | None" = None,
    pace: float = 1.0,
    admission: "AdmissionController | None" = None,
    tail: str = "flush",
    seed: int = 0,
    control=None,
    e2e_hint: float = 0.05,
    reference: bool = False,
    fast_path: bool = True,
    obs=None,
    faults=None,
) -> PipelineResult:
    """Co-simulate ``n_frames`` frames through ``stages`` along ``dag``.

    Exactly one of ``issue`` (open-loop: pre-drawn sorted issue times) and
    ``clients`` (event-interleaved closed loop paced by completions; ``pace``
    staggers the initial slot starts) must be given.  ``admission`` sheds at
    the issue instant against live state.  ``tail`` governs end-of-stream
    leftovers on timeout-less machines (``"flush"`` / ``"drop"``).

    ``control`` (a `repro.serving.control.ControlRuntime`) runs the
    incremental control plane *inside* the loop: it observes every issued
    frame, fires at epoch boundaries (``_K_EPOCH`` events, after all
    same-instant arrivals/frees/flushes), and hot-swaps the stage machine
    sets via :meth:`ModuleStage.apply_update` without dropping in-flight
    frames.  The epoch chain dies once the whole stream has been issued, so
    end-of-stream quiescence (and golden equivalence with the control loop
    disabled) is untouched.  ``e2e_hint`` is the fallback latency estimate
    for clients whose retry ``backoff`` re-reads live plan state.

    ``obs`` (a `repro.serving.observability.Observability`, or None) is the
    passive telemetry sink: the loop reports batch spans, flush causes,
    sheds, parks, and epoch boundaries to it but never reads it back —
    results are bit-identical with observability on or off.

    ``faults`` (a `repro.serving.faults.FaultRuntime`, or None) arms the
    seeded fault injector: ``_K_FAULT`` events crash machines silently
    (dispatch keeps feeding them — nobody knows yet), slow stragglers, and
    drive the batch-duration watchdog that escalates a machine suspect →
    dead; a dead machine's unfinished members are re-queued to surviving
    siblings and the control plane (when present) force-replans the module
    out of band.  Frame conservation holds under any fault schedule:
    every frame still resolves completed, shed, or dropped.
    """
    if tail not in ("flush", "drop"):
        raise ValueError(f"unknown tail policy {tail!r}")
    if (issue is None) == (clients is None):
        raise ValueError("need exactly one of issue= (open loop) or clients=")
    if issue is not None:
        issue = np.asarray(issue, dtype=np.float64)
        if issue.shape != (n_frames,):
            raise ValueError("issue times must have one entry per frame")
    if (
        not reference
        and fast_path
        and issue is not None
        and admission is None
        and control is None
        and faults is None
    ):
        from . import fastpath

        if fastpath.eligible(dag, stages):
            # the whole run is one quiescent segment: delegate to the
            # vectorized replay kernel (the PR-3 equivalence theorem, cached;
            # streams run in the event loop's causal order, backdated
            # end-of-stream tails included — see fastpath docstring)
            return fastpath.run_flat_segment(
                dag, stages, n_frames, issue, tail, obs=obs
            )
    rng = np.random.default_rng(seed)
    topo = dag.topo_order()
    torder = {m: i for i, m in enumerate(topo)}
    parents = {m: sorted(dag.parents(m), key=torder.__getitem__) for m in topo}
    children = {m: sorted(dag.children(m), key=torder.__getitem__) for m in topo}
    sources = [m for m in topo if not parents[m]]
    sink_set = {m for m in topo if not children[m]}
    ancestors = dag.ancestor_closure()

    def holds_real_work(st: ModuleStage) -> bool:
        """True while the stage can still emit completions downstream:
        parked deliveries, busy/queued cores (backpressure-blocked machines
        stay busy with no pending free event), or real formation members.
        Phantom-only buffers are excluded — they discard, never deliver."""
        if st.parked:
            return True
        for core in st.cores.values():
            if core.busy or core.queue:
                return True
            if core.buf and any(i.real for i in core.buf):
                return True
        return False

    # -- per-frame state: preallocated SoA columns indexed by frame id ------
    ft = FrameTable(n_frames, topo, parents, len(sink_set))
    issue_t, shed, lost, resolved = ft.issue, ft.shed, ft.lost, ft.resolved
    sink_bad, sink_max, sinks_left, e2e = (
        ft.sink_bad, ft.sink_max, ft.sinks_left, ft.e2e,
    )
    avail, finish, pend = ft.avail, ft.finish, ft.pend
    parents_left, child_void, child_avail = (
        ft.parents_left, ft.child_void, ft.child_avail,
    )
    stalled, fan = ft.stalled, ft.fan
    # wire the stages' telemetry sinks: the always-on partial-flush forensic
    # column, and the optional observability hooks
    for st_ in stages.values():
        st_.flushed_col = ft.flushed
        st_.obs = obs

    attempts = 0
    next_frame = 0      # closed-loop global frame counter
    issued = 0          # distinct frames offered so far (first attempts)
    # per-stage stream accounting, so phantom injection knows when a stage's
    # real stream is over: a stage is *done* once every frame is accounted
    # there (entered, voided upstream, or shed at ingress) and no instance
    # is still pending — a real frontend stops injecting dummies into a
    # stage whose traffic has ended, and a self-perpetuating phantom chain
    # would otherwise keep the heap non-empty forever
    acc_count = {m: 0 for m in topo}
    pend_total = {m: 0 for m in topo}

    def stage_stream_done(m: str) -> bool:
        return acc_count[m] >= n_frames and pend_total[m] == 0

    # one global heap of (t, kind, seq, stage, payload): ``seq`` is the
    # global push counter, so ties at one instant resolve in push order and
    # no comparison ever reaches the payload
    heap: list = []
    heappush, heappop = heapq.heappush, heapq.heappop
    _seq = 0
    # heap entries that cannot move a real frame: phantom-chain ticks and
    # the frees of phantom-only batches.  The loop never runs on these
    # alone (see the phantom handler): a stage waiting on the quiescence
    # flush must not be starved by phantom traffic elsewhere
    n_idle = 0

    def push(t: float, kind: int, stage: "str | None", payload) -> None:
        nonlocal _seq, n_idle
        if kind == _K_ARRIVE:
            if payload[0] == "phantom":
                n_idle += 1
        elif kind == _K_FREE and not payload[1]:
            n_idle += 1
        heappush(heap, (t, kind, _seq, stage, payload))
        _seq += 1

    def pop() -> tuple:
        nonlocal n_idle
        e = heappop(heap)
        if e[1] == _K_ARRIVE:
            if e[4][0] == "phantom":
                n_idle -= 1
        elif e[1] == _K_FREE and not e[4][1]:
            n_idle -= 1
        return e

    # upstream machines held busy by undelivered outputs: (stage, mid) -> count
    blocked: dict[tuple[str, int], int] = {}

    def think() -> float:
        if clients is None or clients.think_time <= 0.0:
            return 0.0
        if clients.think_dist == "const":
            return clients.think_time
        return float(rng.exponential(clients.think_time))

    def revive_phantoms(st: ModuleStage, now: float) -> None:
        """Restart a dormant injection chain (paid-up through ``now``).

        A chain goes dormant when the stage cannot take a phantom (full,
        parked deliveries, or queued real batches); it must be revived by
        whatever clears that condition — a delivery (the pre-existing hook)
        or a machine freeing (drains the service backlog).  A stage whose
        real stream has ended but whose tail batch still needs phantom fill
        depends on the free-side revival: no further delivery will come.
        """
        if st.phantom_paused and st.phantom_target > 0.0:
            st.phantom_paused = False
            period = 1.0 / st.phantom_target
            st.anchor = now - st.delivered * period
            push(now + period, _K_ARRIVE, None, ("phantom", st.name, st.phantom_token))

    def deliver_to(st: ModuleStage, inst: Instance, now: float) -> None:
        """Deliver one instance and revive a dormant phantom chain."""
        st.deliver(inst, now, push)
        revive_phantoms(st, now)

    def finish_frame(f: int, t: float) -> None:
        if resolved[f]:
            return
        resolved[f] = True
        if not sink_bad[f] and not lost[f]:
            e2e[f] = sink_max[f] - issue_t[f]
        if clients is not None:
            push(t + think(), _K_ARRIVE, None, ("issue", -1, 0))

    def stage_resolved(m, f, t, done, entries, blocker) -> None:
        """Frame ``f`` resolved at stage ``m`` (``done`` or void); propagate."""
        if m in sink_set:
            if done:
                sink_max[f] = max(sink_max[f], t)
            else:
                sink_bad[f] = True
            sinks_left[f] -= 1
            if sinks_left[f] == 0:
                finish_frame(f, t)
        for c in children[m]:
            if done:
                child_avail[c][f] = max(child_avail[c][f], t)
            else:
                child_void[c][f] = True
            parents_left[c][f] -= 1
            if parents_left[c][f] == 0:
                if child_void[c][f]:
                    # a skipped/lost parent voids the child: the frame never
                    # traverses it (seed semantics: finish 0 propagates drop)
                    acc_count[c] += 1
                    stage_resolved(c, f, t, False, entries, blocker)
                else:
                    entries.append((c, f, child_avail[c][f], blocker))

    def enter_stage(m, f, t, blocker, entries, now) -> None:
        """Frame ``f`` becomes available at ``m``; materialize its instances."""
        acc_count[m] += 1
        st = stages[m]
        c = st.fanout.count(f)
        if c == 0:
            # zero-fanout skip: vacuously resolved, excluded downstream
            stage_resolved(m, f, t, False, entries, blocker)
            return
        avail[m][f] = t
        pend[m][f] = c
        fan[m][f] = c
        pend_total[m] += c
        if (
            not reference
            and st.queue_cap is None
            and not st.parked
            and st.phantom_target <= 0.0
            and st.machines
        ):
            # macro-event delivery: the whole fanout enters through one
            # dispatcher walk advance (scalar-identical; see deliver_run) —
            # backpressure parks per-instance and phantom pacing counts
            # per-delivery, so those regimes keep the scalar path
            st.deliver_run(f, c, t, push)
            return
        for _ in range(c):
            inst = Instance(f, t)
            if st.parked or not st.has_space or not st.machines:
                # a stage with NO machines (every one declared dead, no
                # replacement yet) parks blocker-less: a recovery update
                # rescues the queue, and frames still parked at end of run
                # wedge into ``dropped`` (graceful degradation, conserved)
                if not st.machines and faults is not None:
                    ft.failed[f] = True  # victim of the failure, for forensics
                st.parked.append((inst, blocker))
                stalled[f] = True
                if obs is not None:
                    obs.park(t, m)
                if blocker is not None:
                    blocked[blocker] = blocked.get(blocker, 0) + 1
            else:
                deliver_to(st, inst, t)

    def deliver_entries(entries, now) -> None:
        """Deliver newly-available frames, frame-ordered within each stage —
        the order a stable ready-sort would produce."""
        for c, f, t, blocker in sorted(
            entries, key=lambda e: (torder[e[0]], e[1])
        ):
            enter_stage(c, f, t, blocker, entries_out := [], now)
            if entries_out:
                deliver_entries(entries_out, now)

    def drain_parked(st: ModuleStage, now: float) -> bool:
        delivered = False
        while st.parked and st.has_space and st.machines:
            inst, blocker = st.parked.popleft()
            deliver_to(st, inst, now)
            delivered = True
            if blocker is not None:
                unblock(blocker, now)
        return delivered

    def unblock(key: tuple, now: float) -> None:
        blocked[key] -= 1
        if blocked[key] == 0:
            del blocked[key]
            um, umid = key
            ust = stages[um]
            ucore = ust.cores.get(umid)
            if ucore is None or ucore.failed:
                return  # the producer was declared dead while blocked
            ucore.free(now)
            if ust.start_next(umid, now, push):
                drain_parked(ust, now)
            revive_phantoms(ust, now)

    def handle_instance_drop(m, f, t, entries) -> None:
        pend[m][f] -= 1
        pend_total[m] -= 1
        if pend[m][f] == 0:
            if math.isnan(finish[m][f]):
                lost[f] = True
                if obs is not None:
                    obs.shed(t, "pipeline_drop")
                stage_resolved(m, f, t, False, entries, None)
            else:
                # partial completion: the frame proceeds with the instances
                # that did finish (seed semantics: finish = max over done)
                stage_resolved(m, f, float(finish[m][f]), True, entries, None)

    # -- fault injection / detection / recovery ------------------------------
    def active_machines() -> "list[tuple[str, int]]":
        """Crash candidates: every dispatching (non-draining, non-fenced)
        machine, in deterministic (topo, mid) order."""
        out = []
        for m in topo:
            st = stages[m]
            for mach in st.machines:
                core = st.cores.get(mach.mid)
                if core is not None and not core.failed:
                    out.append((m, mach.mid))
        return out

    def declare_dead(m: str, mid: int, t: float) -> None:
        """Failure verdict: fence the machine, re-queue its work, recover.

        The stage surrenders the dead machine's unfinished real members
        (`ModuleStage.fail_machine`); each is marked in the forensic
        ``failed`` column and re-delivered to surviving siblings — or
        parked (blocker-less) when none survive, to be rescued by the
        recovery update's replacement machines.  With a control runtime,
        the module is force-replanned out of band against the reduced
        machine set (`ControlRuntime.on_failure`); without one, recovery
        is requeue-only.  A machine an epoch swap already retired from
        dispatch is reclaimed without the replan (its capacity was already
        replaced by the swap — only its stranded members need rescue).
        """
        st = stages[m]
        faults.forget(m, mid)
        if (m, mid) in faults.dead or st.cores.get(mid) is None:
            return  # verdict already delivered, or the core fully retired
        faults.dead.add((m, mid))
        in_dispatch = any(mach.mid == mid for mach in st.machines)
        reals = st.fail_machine(mid, t)
        faults.n_killed += 1
        if obs is not None:
            obs.fail(t, m, mid)
        faults.n_requeued += len(reals)
        if reals:
            for inst in reals:
                ft.failed[inst.frame] = True
            if obs is not None:
                obs.requeue(t, m, mid, len(reals))
        for inst in reals:
            if st.machines and st.has_space and not st.parked:
                deliver_to(st, inst, t)
            else:
                st.parked.append((inst, None))
                if obs is not None:
                    obs.park(t, m)
        if control is not None and in_dispatch and issued < n_frames:
            updates = control.on_failure(t, m)
            if updates:
                for um, upd in updates.items():
                    stages[um].apply_update(upd, t, push)
                for um in updates:
                    drain_parked(stages[um], t)

    def inject_fault(fkind: str, t: float) -> None:
        """Fire one fault.  Crashes are *silent* — the core is fenced but
        stays in the dispatch walk until the watchdog declares it dead —
        because nobody in a real cluster learns of a crash except through
        missed heartbeats.  Device loss crashes every co-located slot of
        one physical device at once and repacks the shared pool
        immediately (the hardware monitor's out-of-band signal)."""
        cfg = faults.cfg
        if fkind == "device_loss" and cfg.device_map:
            did = faults.pick(sorted(set(cfg.device_map.values())))
            hit = False
            for (m, mid), d in sorted(cfg.device_map.items()):
                if d != did:
                    continue
                st = stages.get(m)
                core = st.cores.get(mid) if st is not None else None
                if core is not None and not core.failed:
                    core.failed = True
                    hit = True
            if hit:
                faults.n_injected += 1
                if cfg.on_device_loss is not None:
                    cfg.on_device_loss(t, did)
            return
        cand = active_machines()
        if fkind == "straggler":
            victim = faults.pick(cand)
            if victim is not None:
                m, mid = victim
                faults.slow[(m, mid)] = cfg.straggler_factor
                faults.n_injected += 1
                push(t + cfg.straggler_duration, _K_FAULT, m, ("recover", mid))
            return
        # "crash" (and device_loss outside a shared pool): without a control
        # plane no replacement ever comes, so prefer a stage that keeps at
        # least one survivor — a single-machine stage would wedge its whole
        # app until end-of-stream
        if control is None:
            multi = [(m, mid) for m, mid in cand if len(stages[m].machines) > 1]
            cand = multi or cand
        victim = faults.pick(cand)
        if victim is not None:
            m, mid = victim
            stages[m].cores[mid].failed = True
            faults.n_injected += 1

    def issue_frame(f: int, t: float, tries: int) -> None:
        nonlocal attempts, issued
        if clients is not None:
            attempts += 1
        if tries == 0:
            issued += 1
            if control is not None:
                # the control plane estimates demand from *offered* frames:
                # shed traffic is still demand the next plan should cover
                control.observe(t)
        if admission is not None:
            # live ingress occupancy: instances waiting (formation + queued
            # + parked) at the source stages — the real quantity the PR-2
            # virtual drain-rate queue approximated
            backlog = sum(
                stages[src].backlog + len(stages[src].parked) for src in sources
            )
            # interim denials the closed-loop client will re-issue are
            # tagged "shed_retry", never "shed": trace/metrics "shed"
            # instants stay summable as terminal sheds in both loop shapes
            will_retry = (
                clients is not None
                and clients.retry_on_shed
                and tries < clients.max_retries
            )
            if will_retry:
                cause = "shed_retry"
            elif clients is not None and clients.retry_on_shed and tries > 0:
                cause = "retry_exhausted"  # the bounded-retry budget ran out
            else:
                cause = "shed"
            admitted = admission.admit_live(t, backlog, cause=cause)
        else:
            admitted = True
        if admitted:
            issue_t[f] = t
            entries = []
            for src in sources:
                enter_stage(src, f, t, None, entries, t)
            deliver_entries(entries, t)
            return
        if (
            clients is not None
            and clients.retry_on_shed
            and tries < clients.max_retries
        ):
            # backoff=None re-reads the *live* plan's modeled e2e latency at
            # every retry (per-epoch state under a control loop, not a
            # run-constant): a client waits about one service round
            if clients.backoff is not None:
                base = clients.backoff
            elif control is not None:
                base = control.e2e_hint
            else:
                base = e2e_hint
            delay = base * (2.0 ** tries) * float(rng.uniform(0.5, 1.5))
            push(t + delay, _K_ARRIVE, None, ("issue", f, tries + 1))
            return
        issue_t[f] = t
        exhausted = clients is not None and clients.retry_on_shed and tries > 0
        if exhausted:
            # the bounded retry budget ran out: the frame was offered and
            # re-offered but never entered the pipeline — it counts as
            # *dropped* (admitted demand the system failed), not shed
            # (a first-sight rejection), under its own trace cause
            lost[f] = True
        else:
            shed[f] = True
        if obs is not None and (admission is None or admission.obs is None):
            # a wired admission controller already emitted this terminal
            # denial at decision resolution (interim retry denials carry
            # the distinct "shed_retry" cause); only emit here when the
            # terminal denial would otherwise go unseen
            obs.shed(t, "retry_exhausted" if exhausted else "shed")
        resolve_shed(f, t)

    def resolve_shed(f: int, t: float) -> None:
        resolved[f] = True
        for m in topo:
            acc_count[m] += 1  # a shed frame's stream position is spent
        if clients is not None:
            push(t + think(), _K_ARRIVE, None, ("issue", -1, 0))

    # -- prime the loop ------------------------------------------------------
    t_first = 0.0
    if issue is not None:
        for i in range(n_frames):
            push(float(issue[i]), _K_ARRIVE, None, ("issue", i, 0))
        t_first = float(issue[0]) if n_frames else 0.0
    else:
        slots = clients.n_clients * clients.max_in_flight
        for k in range(min(slots, n_frames)):
            push(k / pace, _K_ARRIVE, None, ("issue", -1, 0))
    for m in topo:
        st = stages[m]
        if st.phantom_target > 0.0:
            st.anchor = t_first
            push(
                t_first + 1.0 / st.phantom_target, _K_ARRIVE, None,
                ("phantom", m, st.phantom_token),
            )
    if faults is not None:
        # one pending injection event at a time; each fired fault chains
        # the next (explicit schedule first, then the seeded MTBF process).
        # The chain retires with the stream, like the epoch chain.
        nf = faults.next_fault(t_first)
        if nf is not None:
            push(nf[0], _K_FAULT, None, ("inject", nf[1]))
        wd_k = faults.cfg.detect_k

        def arm_watchdog(m: str, mid: int, core, now: float) -> None:
            # heartbeat: batch #n_closed must complete (n_done reaches it)
            # within k x the machine's modeled service, else escalate
            push(
                now + wd_k * core.machine.config.duration,
                _K_FAULT, m, ("watchdog", mid, core.n_closed, core.n_done),
            )

        for st_ in stages.values():
            st_.watchdog = arm_watchdog
            st_.keep_spare = faults.cfg.spare

    epoch_armed = False
    relax_armed = False
    relax_every = control.relax_interval if control is not None else None
    if control is not None:
        push(control.next_epoch(t_first), _K_EPOCH, None, None)
        epoch_armed = True
        if relax_every is not None:
            # mid-epoch staleness ticks: transient-aware deadline relaxation
            # (same event kind as epochs — a swap at the same instant must
            # observe everything — distinguished by payload)
            push(t_first + relax_every, _K_EPOCH, None, ("relax",))
            relax_armed = True

    # -- main loop -----------------------------------------------------------
    t_now = 0.0
    while True:
        if not heap:
            # stream quiescent: resolve leftover partial batches (the
            # single-module core does this once at end of stream; clients can
            # also quiesce mid-run when every slot waits on a stuck frame —
            # flushing is then the only causally-consistent way forward).
            # Per round, flush every stage whose ANCESTORS hold no more
            # real work: an upstream tail flush can still deliver members
            # that complete a downstream batch, so a stage must not flush
            # until everything above it has fully drained (the replay kernel
            # runs whole modules in topo order for exactly this reason).
            # Sibling stages, however, must flush in the SAME round: their
            # tail completions re-enter the heap and process in global time
            # order, so a shared child receives them in availability order
            # — flushing one sibling per round delivered a later-flushed
            # sibling's EARLIER completion after an earlier-flushed
            # sibling's later one, silently reordering the child's dispatch
            # stream relative to the replay kernel's stable ready-sort.
            acted = False
            # frozen per round: a child must not flush in the round its
            # ancestor's tail closed — that tail's completion still has to
            # travel through the heap and may complete the child's batch
            stage_busy = {m: holds_real_work(stages[m]) for m in topo}
            for m in topo:
                if any(stage_busy[a] for a in ancestors[m]):
                    continue  # an upstream tail can still feed this stage
                st = stages[m]
                entries: list = []
                for mid, core in st.cores.items():
                    if not core.buf:
                        continue
                    reals = [i for i in core.buf if i.real]
                    if reals and core.timeout is not None:
                        continue  # an armed deadline event is still coming
                    if reals and tail == "flush":
                        # flush at the last REAL member's ready time: the
                        # frontend stops injecting phantoms once the stream
                        # ends (single-module reference semantics)
                        t_last = max(i.ready for i in reals)
                        st.close(
                            mid, batch_ready=t_last, now=t_last, push=push,
                            cause="eos",
                        )
                    else:
                        for inst in st.discard_leftover(mid):
                            handle_instance_drop(m, inst.frame, t_now, entries)
                    acted = True  # the non-empty buffer was emptied either way
                if entries:
                    deliver_entries(entries, t_now)
                acted |= drain_parked(st, t_now)
            if not acted and not heap:
                break
            if acted and control is not None and issued < n_frames:
                # the wedge is resolved and the run continues: re-arm the
                # epoch/relax chains that lapsed to let this flush happen
                if not epoch_armed:
                    push(control.next_epoch(t_now), _K_EPOCH, None, None)
                    epoch_armed = True
                if relax_every is not None and not relax_armed:
                    push(t_now + relax_every, _K_EPOCH, None, ("relax",))
                    relax_armed = True
            continue
        t, kind, _s, stage_name, payload = pop()
        t_now = max(t_now, t)
        if kind == _K_ARRIVE:
            what = payload[0]
            if what == "issue":
                _, f, tries = payload
                if f == -1:
                    if next_frame >= n_frames:
                        continue  # stream exhausted: slot retires
                    f, tries = next_frame, 0
                    next_frame += 1
                issue_frame(f, t, tries)
            else:  # adaptive phantom injection at one stage
                _, m, token = payload
                st = stages[m]
                if token != st.phantom_token or st.phantom_target <= 0.0:
                    continue  # a hot-swap re-anchored the streamer: stale chain
                if stage_stream_done(m):
                    continue  # this stage's real stream is over: chain dies
                if n_idle == len(heap):
                    # nothing but phantom traffic is left to happen: padding
                    # now would only fill batches that no real event will
                    # ever come to serve (or hold a timeout-less tail past
                    # its last real arrival).  Go dormant, so the loop
                    # reaches the quiescence flush; the next delivery or
                    # free revives the chain
                    st.phantom_paused = True
                    continue
                period = 1.0 / st.phantom_target
                if st.delivered == 0:
                    # pad only from the first real arrival onward (the
                    # injector spans the real stream): go dormant rather
                    # than warm an idle stage — or keep the heap alive while
                    # an upstream wedge waits for the quiescence flush; the
                    # first delivery revives the chain (deliver_to)
                    st.phantom_paused = True
                    continue
                # half-slot grace: upstream batch completions land in bursts
                # that tie with the slot boundary (arrivals pop before
                # same-time frees), so only a genuine >1.5-slot lag pads
                due = st.anchor + (st.delivered + 1.5) * period
                if t >= due - 1e-12:
                    # collection fell behind target * elapsed: pad with one
                    # phantom (`frontend.dummy.phantom_times`' deficit
                    # padding expressed causally), then resync the anchor so the stage is
                    # considered paid-up through now — old deficit is
                    # forgiven rather than burst-injected, and total
                    # arrivals stay rate-limited at the target.  A stage
                    # with queued real batches gets no phantoms: idle-slot
                    # filling must not eat the capacity that drains backlog
                    if (st.has_space and not st.parked and st.machines
                            and not st.service_backlog):
                        st.stats.phantom += 1
                        if obs is not None:
                            obs.phantom(t, m)
                        st.deliver(Instance(-1, t), t, push)
                    else:
                        # full stage: go dormant instead of re-pushing — a
                        # self-perpetuating chain would keep the heap alive
                        # forever while the wedged stage waits for the
                        # quiescence flush that only an empty heap triggers;
                        # the next delivery revives the chain (deliver_to)
                        st.phantom_paused = True
                        continue
                    st.anchor = t - st.delivered * period
                    push(t + period, _K_ARRIVE, None, ("phantom", m, st.phantom_token))
                else:
                    # real arrivals kept the collect rate at target: check
                    # again when the next slot comes due
                    push(due, _K_ARRIVE, None, ("phantom", m, st.phantom_token))
        elif kind == _K_FREE:
            # collect every machine-free at this instant before delivering,
            # so cross-machine outputs land downstream in frame order
            frees = [(stage_name, payload[0])]
            while heap and heap[0][0] == t and heap[0][1] == _K_FREE:
                nxt = pop()
                frees.append((nxt[3], nxt[4][0]))
            if faults is not None:
                # fence dead machines: a fenced core's "completion" never
                # happened (its members are re-queued at the failure
                # verdict); live completions advance the watchdog heartbeat
                # and clear any straggler suspicion
                live = []
                for m, mid in frees:
                    core = stages[m].cores.get(mid)
                    if core is None or core.failed:
                        continue
                    core.n_done += 1
                    faults.clear(m, mid)
                    live.append((m, mid))
                frees = live
                if not frees:
                    continue
            entries = []
            finished: list[tuple[str, int, int]] = []
            for m, mid in frees:
                st = stages[m]
                members = st.in_service.pop(mid)
                for inst in members:
                    if not inst.real:
                        continue
                    f = inst.frame
                    st.stats.latencies.append(t - inst.ready)
                    pend[m][f] -= 1
                    pend_total[m] -= 1
                    fm = finish[m]
                    fm[f] = t if math.isnan(fm[f]) else max(fm[f], t)
                    if pend[m][f] == 0:
                        finished.append((m, mid, f))
            for m, mid, f in finished:
                stage_resolved(m, f, float(finish[m][f]), True, entries, (m, mid))
            deliver_entries(entries, t)
            # two passes: free every machine whose outputs fully delivered,
            # THEN drain backpressured stages.  A drain can unblock (free +
            # restart) a machine whose own free event sits in this very
            # batch — freeing it again afterwards would double-start it.
            for m, mid in frees:
                st = stages[m]
                if blocked.get((m, mid), 0) == 0:
                    st.cores[mid].free(t)
                    st.start_next(mid, t, push)
                # else: outputs parked downstream — the machine stays busy
                # until the backpressured stage drains (see unblock)
            for m, mid in frees:
                drain_parked(stages[m], t)
            for m in {m for m, _ in frees}:
                # a free may have cleared the service backlog that paused
                # the stage's phantom chain — the last real tail batch can
                # only fill if the chain comes back without a new delivery
                revive_phantoms(stages[m], t)
        elif kind == _K_FLUSH:
            st = stages[stage_name]
            mid, token = payload
            core = st.cores.get(mid)  # None: the core retired after a drain
            if core is not None and token == core.token and core.buf:
                st.close(mid, batch_ready=t, now=t, push=push, cause="deadline")
                drain_parked(st, t)
        elif kind == _K_FAULT:
            what = payload[0]
            if what == "inject":
                if issued >= n_frames:
                    continue  # stream fully issued: the injector retires
                inject_fault(payload[1], t)
                nf = faults.next_fault(t)
                if nf is not None:
                    push(nf[0], _K_FAULT, None, ("inject", nf[1]))
            elif what == "watchdog":
                _, mid, seq, done_at_arm = payload
                m = stage_name
                st = stages[m]
                core = st.cores.get(mid)
                if core is None or (m, mid) in faults.dead:
                    continue  # retired, or verdict already delivered
                if not core.failed and all(mc.mid != mid for mc in st.machines):
                    # a *healthy* machine drained out of dispatch serves its
                    # queue to completion: unwatched.  A crashed one stays
                    # watched even after an epoch swap retires it — its
                    # stranded members still need the failure verdict to be
                    # reclaimed and re-queued.
                    continue
                if core.n_done >= seq:
                    faults.clear(m, mid)  # heartbeat satisfied in time
                elif core.n_done > done_at_arm:
                    # progress since arming — the watched batch is queued
                    # behind earlier work, not stuck: extend the deadline
                    push(
                        t + wd_k * core.machine.config.duration,
                        _K_FAULT, m, ("watchdog", mid, seq, core.n_done),
                    )
                elif faults.escalate(m, mid) == "suspect":
                    if obs is not None:
                        obs.suspect(t, m, mid)
                    push(
                        t + wd_k * core.machine.config.duration,
                        _K_FAULT, m, ("watchdog", mid, seq, core.n_done),
                    )
                else:  # second missed heartbeat while suspect: dead
                    declare_dead(m, mid, t)
            else:  # "recover": a straggler's transient slowdown expires
                faults.slow.pop((stage_name, payload[1]), None)
        else:  # _K_EPOCH: control-plane boundary (after same-instant events)
            if payload is not None and payload[0] == "relax":
                # mid-epoch staleness tick: when arrivals run well below the
                # active plan's provisioned rate, re-resolve every stage's
                # flush deadlines with the collect rate scaled to observed
                # (open batches keep their members and arming instants)
                relax_armed = False
                if issued >= n_frames:
                    continue  # the tick chain retires with the stream
                if control.on_tick(t) is not None:
                    for m in topo:
                        st = stages[m]
                        st.retime(control.relax_timeout(m, st.machines), t, push)
                if heap:
                    push(t + relax_every, _K_EPOCH, None, ("relax",))
                    relax_armed = True
                continue
            epoch_armed = False
            if issued >= n_frames:
                continue  # stream fully issued: the epoch chain retires,
                #           end-of-stream quiescence proceeds untouched
            updates = control.on_epoch(t)
            if obs is not None:
                # flush the closing window's metrics under the machine set
                # that served it (the swap below applies the next window's)
                obs.epoch(
                    t, control.history[-1],
                    {m: len(stages[m].machines) for m in topo},
                )
            if updates:
                for m, upd in updates.items():
                    stages[m].apply_update(upd, t, push)
                for m in updates:
                    # swapped-in machines are idle: parked/backpressured
                    # deliveries may proceed immediately
                    drain_parked(stages[m], t)
            if heap:
                push(control.next_epoch(t), _K_EPOCH, None, None)
                epoch_armed = True
            # an otherwise-empty heap means the run is wedged on a partial
            # batch that only the quiescence flush (which requires an empty
            # heap) can resolve: let the chain lapse; the flush re-arms it

    return ft.finalize(dag, {m: stages[m].stats for m in topo}, attempts)
