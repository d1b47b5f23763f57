"""Composable DAG stages: one module's machines behind a bounded ingress.

A :class:`ModuleStage` wraps the single-machine cores of
`repro.serving.events.MachineCore` into one DAG stage: an *incremental*
dispatcher assigns instances to machines in arrival order (the streaming
form of `core.dispatch.dispatch_runs` — the static run-length walk cannot be
precomputed because the pipelined arrival stream only exists as the
co-simulation unfolds), formation buffers fill/flush exactly like the
single-module reference core, and a bounded ingress backlog exerts
**backpressure**: when ``queue_cap`` instances are already waiting to start
service, further deliveries park FIFO and the *upstream machine that
produced them stays busy* until the stage drains — the cross-stage
interference Harpagon's per-module WCL sums cannot see.

The stage owns no event loop; `repro.serving.pipeline.core` drives every
stage of the app DAG from one global heap.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field, replace as _dc_replace
from typing import Callable, Mapping, Sequence

from ...core.dispatch import Machine, Policy
from ..events import MachineCore


class Instance:
    """One module-level request of one frame (``frame == -1``: phantom)."""

    __slots__ = ("frame", "ready")

    def __init__(self, frame: int, ready: float = 0.0):
        self.frame = frame
        self.ready = ready

    @property
    def real(self) -> bool:
        return self.frame >= 0


class TCDispatcher:
    """Incremental weighted-fair batch walk (Harpagon TC dispatch).

    Machine *i* owns periodic run slots at ``k * b_i / f_i`` merged by
    ``(slot time, -ratio, index)``; consecutive arrivals fill the current
    run (one batch) before the walk advances — request-for-request identical
    to `core.dispatch.dispatch_runs(policy=TC)` on the same stream.

    :meth:`update` swaps the machine set *without restarting the walk*
    (control-plane hot swap): kept machines keep their virtual-time slot
    positions and the open run keeps filling, so a partially-formed batch
    is never stranded; added machines join at the walk's current frontier
    (`dispatch.remaining_workloads` semantics — a new machine starts
    collecting its slice of the stream immediately).
    """

    def __init__(self, machines: Sequence[Machine]):
        self.machines = list(machines)
        self._next_t = {m.mid: 0.0 for m in machines}
        self._cur: "int | None" = None  # mid of the machine with an open run
        self._left = 0

    def assign(self) -> int:
        if self._left == 0:
            i = min(
                range(len(self.machines)),
                key=lambda j: (
                    self._next_t[self.machines[j].mid],
                    -self.machines[j].config.ratio,
                    j,
                ),
            )
            m = self.machines[i]
            self._cur = m.mid
            self._left = m.config.batch
            self._next_t[m.mid] += m.config.batch / m.rate
        self._left -= 1
        return self._cur

    def assign_run(self, count: int) -> "list[tuple[int, int]]":
        """Assign ``count`` consecutive arrivals in one walk advance.

        Returns run-length pairs ``[(mid, k)]`` — exactly the machines the
        scalar :meth:`assign` would have produced for ``count`` successive
        calls, but advancing the virtual-time walk run-by-run instead of
        request-by-request (the macro-event form of the TC walk: one
        ``min()`` per *batch*, not per instance)."""
        runs: list[tuple[int, int]] = []
        while count > 0:
            if self._left == 0:
                i = min(
                    range(len(self.machines)),
                    key=lambda j: (
                        self._next_t[self.machines[j].mid],
                        -self.machines[j].config.ratio,
                        j,
                    ),
                )
                m = self.machines[i]
                self._cur = m.mid
                self._left = m.config.batch
                self._next_t[m.mid] += m.config.batch / m.rate
            k = self._left if self._left < count else count
            self._left -= k
            count -= k
            if runs and runs[-1][0] == self._cur:
                runs[-1] = (self._cur, runs[-1][1] + k)
            else:
                runs.append((self._cur, k))
        return runs

    def update(self, machines: Sequence[Machine]) -> None:
        old = self._next_t
        self.machines = list(machines)
        frontier = min(
            (old[m.mid] for m in machines if m.mid in old), default=0.0
        )
        self._next_t = {m.mid: old.get(m.mid, frontier) for m in machines}
        if self._cur is not None and self._cur not in self._next_t:
            self._left = 0  # the open run's machine drained: abandon the run


class RRDispatcher:
    """Deficit-counter weighted round-robin of individual requests (RR/DT),
    request-for-request identical to `dispatch_runs` under those policies.
    :meth:`update` preserves kept machines' deficit credits across a swap."""

    def __init__(self, machines: Sequence[Machine]):
        self.machines = list(machines)
        self._credit = {m.mid: 0.0 for m in machines}
        self._tot = sum(m.rate for m in self.machines)

    def assign(self) -> int:
        for m in self.machines:
            self._credit[m.mid] += m.rate / self._tot
        j = max(range(len(self.machines)), key=lambda i: self._credit[self.machines[i].mid])
        mid = self.machines[j].mid
        self._credit[mid] -= 1.0
        return mid

    def assign_run(self, count: int) -> "list[tuple[int, int]]":
        """Deficit walk for ``count`` arrivals, merged into run-length pairs
        (scalar-identical; RR interleaves, so runs are usually length 1)."""
        runs: list[tuple[int, int]] = []
        for _ in range(count):
            mid = self.assign()
            if runs and runs[-1][0] == mid:
                runs[-1] = (mid, runs[-1][1] + 1)
            else:
                runs.append((mid, 1))
        return runs

    def update(self, machines: Sequence[Machine]) -> None:
        old = self._credit
        self.machines = list(machines)
        self._credit = {m.mid: old.get(m.mid, 0.0) for m in machines}
        self._tot = sum(m.rate for m in self.machines)


def make_dispatcher(machines: Sequence[Machine], policy: Policy):
    if policy is Policy.TC:
        return TCDispatcher(machines)
    return RRDispatcher(machines)


@dataclass
class StageStats:
    """Per-stage accounting, mirror of the engine's ``ModuleStats`` fields."""

    latencies: list[float] = field(default_factory=list)
    batches: int = 0
    dropped: int = 0
    phantom: int = 0


@dataclass
class StageUpdate:
    """One stage's share of a plan hot-swap (control-plane epoch).

    ``machines`` is the *target* machine set of the new schedule (mids as
    produced by ``expand_machines`` — the stage remaps them onto its own
    stable core ids); ``timeout`` is keyed by those same mids.
    ``phantom_target`` is the new provisioned collect rate for the adaptive
    dummy streamer (0 = stop streaming).
    """

    machines: Sequence[Machine]
    timeout: "float | None | Mapping[int, float]" = None
    phantom_target: float = 0.0


class ModuleStage:
    """One DAG module as a pipeline stage: dispatcher + cores + backlog.

    ``timeout`` is a single flush deadline or a per-machine-id mapping (the
    engine's ``"budget"`` resolution).  ``phantom_target`` > 0 streams the
    plan's priced phantom traffic *adaptively*: the stage pads batch
    formation up to that total collect rate (``sum(rate + dummy)``), so a
    phantom is injected only when real traffic has left a gap — the
    event-interleaved analogue of the pad-to-provisioned injector
    `frontend.dummy.phantom_times`.  ``queue_cap`` bounds the number of
    real instances waiting to start service; ``None`` means unbounded (no
    backpressure).
    """

    def __init__(
        self,
        name: str,
        machines: Sequence[Machine],
        policy: Policy,
        *,
        timeout: "float | None | Mapping[int, float]" = None,
        fanout=None,
        phantom_target: float = 0.0,
        queue_cap: "int | None" = None,
        service_time=None,
        service_obs: "Callable | None" = None,
    ):
        if queue_cap is not None and queue_cap < 1:
            raise ValueError("queue_cap must be >= 1 (or None for unbounded)")
        self._req_queue_cap = queue_cap  # as requested, pre-floor (re-floored on swap)
        if queue_cap is not None:
            # formation buffers count toward the backlog, so a cap below the
            # largest batch size could never form a full batch: floor it
            queue_cap = max(queue_cap, max(m.config.batch for m in machines))
        if isinstance(timeout, Mapping):
            t_of = {m.mid: timeout.get(m.mid) for m in machines}
        else:
            t_of = {m.mid: timeout for m in machines}
        self.name = name
        self.machines = list(machines)
        self.policy = policy  # the segment fast-path re-derives dispatch_runs
        self.cores = {m.mid: MachineCore(m, t_of[m.mid]) for m in machines}
        self._next_mid = max((m.mid for m in machines), default=-1) + 1
        self.dispatcher = make_dispatcher(machines, policy)
        self.fanout = fanout
        self.phantom_target = float(phantom_target)
        # phantom pacing state: a phantom is due when `delivered` (real +
        # phantom arrivals since `anchor`) falls behind target * elapsed —
        # total collection is padded up to, and rate-limited at, the target
        self.anchor = 0.0
        self.delivered = 0
        # True while the injection chain is dormant (stage was full): a
        # dormant chain schedules no events, so a wedged pipeline can reach
        # quiescence and flush; the next successful delivery revives it
        self.phantom_paused = False
        # bumped when a hot-swap re-anchors the streamer: pending chain
        # events carry the token they were pushed under and die if stale,
        # so a swap can restart the chain without double-injecting
        self.phantom_token = 0
        self.queue_cap = queue_cap
        # batch service durations: None takes the profiled constant (the
        # bit-exact default); a `serving.service_time.ServiceTimeSource`
        # supplies trace/live wall-clock durations at every batch start.
        # ``service_obs(module, machine, duration, now)`` — when set — sees
        # each started batch's actual duration (the control plane's
        # model-vs-measured estimator feed).
        self.service_time = service_time
        self.service_obs = service_obs
        # observability (`repro.serving.observability`): ``obs`` is the
        # optional hook sink (None = hook-free hot path), ``flushed_col``
        # the FrameTable's always-on partial-flush forensic column — both
        # wired by `pipeline.core.run_pipeline`
        self.obs = None
        self.flushed_col = None
        # fault wiring (`repro.serving.faults`, all None/False without an
        # injector — the hooks are never consulted on the fault-free path):
        # ``watchdog(name, mid, core, now)`` arms a detection heartbeat at
        # every batch close; ``keep_spare`` holds the most-recently-drained
        # machine idle-warm one epoch as failover insurance
        self.watchdog = None
        self.keep_spare = False
        self._spare: "int | None" = None
        self.backlog = 0  # instances delivered but not yet started service
        self.phantoms = 0  # the phantom share of ``backlog``
        # deliveries parked by backpressure: (instance, blocker) where
        # blocker is the (stage, mid) whose outputs they are, or None for
        # ingress arrivals (open-loop frames waiting at the source)
        self.parked: deque = deque()
        self.in_service: dict[int, list[Instance]] = {}
        self.stats = StageStats()

    # -- capacity ------------------------------------------------------------
    @property
    def has_space(self) -> bool:
        """Room for one more delivery under ``queue_cap``.

        Only real instances count: phantoms never hold capacity that real
        deliveries need.  A phantom-only formation buffer arms no deadline,
        so a stage filled to the cap by phantoms would park its real
        deliveries for good while a sibling's phantom chain kept the loop
        alive.  Phantoms stay bounded without the cap: the injector stops
        at parked deliveries and at queued batches (`service_backlog`).
        """
        return self.queue_cap is None or self.backlog - self.phantoms < self.queue_cap

    @property
    def service_backlog(self) -> bool:
        """True when closed batches are queued behind a busy machine.

        The phantom injector checks this: a real frontend fills *otherwise
        idle* batch slots, so while real work is already waiting for service
        the stage must spend its capacity burning that backlog down, not
        serving phantoms — otherwise provisioning slack (a control loop's
        ``margin``) could never drain a transient queue.
        """
        return any(c.queue for c in self.cores.values())

    # -- control-plane hot swap ----------------------------------------------
    def apply_update(self, upd: StageUpdate, now: float, push: Callable) -> None:
        """Apply one epoch's plan delta to the live stage.

        Per configuration, existing cores are kept up to the new machine
        count (work-holding cores first — a draining core of the right
        configuration is revived rather than duplicated); surplus cores are
        marked draining: their open batch closes *now* (flushes with its
        real members; a phantom-only buffer is discarded), already-queued
        batches run to completion, and no new members are dispatched to
        them.  Added machines get fresh stage-local ids and join the
        dispatch walk immediately.  The dispatcher is rebuilt over the new
        active set (the TC walk restarts ratio-aligned), and the dummy
        streamer re-anchors to the new provisioned collect rate.
        """
        if isinstance(upd.timeout, Mapping):
            t_of = {m.mid: upd.timeout.get(m.mid) for m in upd.machines}
        else:
            t_of = {m.mid: upd.timeout for m in upd.machines}

        by_cfg: dict = {}
        for mid, core in self.cores.items():
            by_cfg.setdefault(core.machine.config, []).append(core)
        new_by_cfg: dict = {}
        for m in upd.machines:
            new_by_cfg.setdefault(m.config, []).append(m)

        active: list[Machine] = []
        claimed: set[int] = set()
        for cfg, new_ms in new_by_cfg.items():
            pool = by_cfg.get(cfg, [])
            # keep work-holding cores first; revive draining cores before
            # creating duplicates (their queued work rejoins the same rank)
            # a fenced dead core is never revived — a replacement gets a
            # fresh id (or promotes the warm spare)
            pool = sorted(
                (c for c in pool if not c.failed),
                key=lambda c: (c.draining, c.drained),
            )
            for nm in new_ms:
                if pool:
                    core = pool.pop(0)
                    mid = core.machine.mid
                    if mid == self._spare:
                        # warm-spare promotion: the idle-warm machine
                        # rejoins dispatch instead of a cold add
                        self._spare = None
                        if self.obs is not None:
                            self.obs.promote_spare(now, self.name, mid)
                else:
                    mid = self._next_mid
                    self._next_mid += 1
                    core = MachineCore(_dc_replace(nm, mid=mid), None)
                    self.cores[mid] = core
                machine = _dc_replace(nm, mid=mid)
                core.machine = machine
                core.timeout = t_of.get(nm.mid)
                core.draining = False
                claimed.add(mid)
                active.append(machine)
        for mid, core in self.cores.items():
            if mid in claimed or core.draining:
                continue
            core.draining = True
            if self.obs is not None:
                self.obs.drain(now, self.name, mid)
            if core.buf:
                # drained machines finish their open batch: it closes now
                # (partial) and their queued work runs to completion; a
                # phantom-only buffer is discarded — nothing real is lost
                if any(i.real for i in core.buf):
                    self.close(mid, batch_ready=now, now=now, push=push, cause="drain")
                else:
                    self.discard_leftover(mid)
        # retire cores that finished draining: they hold no work and no
        # live event references them (a busy core cannot be drained; stale
        # flush events tolerate a missing mid), so keeping them would grow
        # the stage without bound across epochs and slow every hot-path
        # scan (service_backlog, quiescence) proportionally to run length
        retire = [
            mid for mid, c in self.cores.items()
            if mid not in claimed and c.draining and c.drained
        ]
        if self.keep_spare:
            # keep the most-recently-drained healthy retiree idle-warm for
            # one epoch (failover insurance — ROADMAP's lazily-drained warm
            # machine); last epoch's spare, if still unclaimed, retires now
            prev = self._spare
            self._spare = None
            cand = [m for m in retire if not self.cores[m].failed and m != prev]
            if cand:
                self._spare = max(cand)
                retire.remove(self._spare)
        for mid in retire:
            del self.cores[mid]
            self.in_service.pop(mid, None)

        self.machines = active
        # the walk continues across the swap: kept machines keep their slot
        # positions (their open formation buffers keep filling — no batch is
        # stranded), added machines join at the frontier
        self.dispatcher.update(active)
        if self._req_queue_cap is not None:
            self.queue_cap = max(
                self._req_queue_cap,
                max((m.config.batch for m in active), default=1),
            )

        target = float(upd.phantom_target)
        retarget = abs(target - self.phantom_target) > 1e-12
        self.phantom_target = target
        if retarget:
            # re-anchor the dummy streamer to the new provisioned rate:
            # paid-up through now, old chain events die on the stale token
            self.phantom_token += 1
            self.phantom_paused = False
            if target > 0.0:
                period = 1.0 / target
                self.anchor = now - self.delivered * period
                push(
                    now + period, _K_ARRIVE, None,
                    ("phantom", self.name, self.phantom_token),
                )

    def retime(
        self,
        timeout: "float | None | Mapping[int, float]",
        now: float,
        push: Callable,
    ) -> None:
        """Swap every active core's flush deadline in place (the control
        plane's mid-epoch deadline relaxation).

        Unlike :meth:`apply_update` this touches no machines and closes no
        batches: each core's open formation buffer keeps its members and its
        arming instant, only the deadline is re-anchored — a pending flush
        dies on the bumped token and the replacement fires at
        ``max(armed_at + new_timeout, now)`` (an already-overdue deadline
        under the *longer* new timeout flushes immediately, never in the
        past).  Draining cores are left alone: their open batch was already
        closed at the drain instant.
        """
        if isinstance(timeout, Mapping):
            t_of = {m.mid: timeout.get(m.mid) for m in self.machines}
        else:
            t_of = {m.mid: timeout for m in self.machines}
        for machine in self.machines:
            mid = machine.mid
            core = self.cores[mid]
            if core.draining:
                continue
            deadline = core.retime(t_of.get(mid))
            if deadline is not None:
                push(max(deadline, now), _K_FLUSH, self.name, (mid, core.token))

    # -- formation / service -------------------------------------------------
    def deliver(self, inst: Instance, now: float, push: Callable) -> None:
        """Hand one instance to the dispatcher at time ``now``.

        ``push(t, kind, stage_name, payload)`` schedules flush/free events on
        the owner's heap.  Caller must have checked :attr:`has_space`.
        """
        inst.ready = now
        self.delivered += 1
        self.backlog += 1
        if inst.frame < 0:
            self.phantoms += 1
        mid = self.dispatcher.assign()
        core = self.cores[mid]
        deadline = core.add(inst, now, inst.real)
        if deadline is not None:
            push(deadline, _K_FLUSH, self.name, (mid, core.token))
        if core.full:
            self.close(mid, batch_ready=now, now=now, push=push)

    def deliver_run(self, frame: int, count: int, now: float, push: Callable) -> None:
        """Hand ``count`` same-instant REAL instances of ``frame`` to the
        dispatcher in one macro-event.

        Scalar-identical to ``count`` successive :meth:`deliver` calls when
        the stage is unbounded (``queue_cap is None``), has nothing parked,
        and streams no phantoms — the caller gates on exactly those
        conditions.  The dispatcher advances run-by-run (one walk step per
        batch) and each run's members join the formation buffer as a block:
        the buffer fills/closes at the same member boundaries, the flush
        deadline arms on the same (first real) member at the same instant,
        and frees are pushed in the same order, so every downstream event
        carries the same ``(t, kind, seq)`` key as the scalar path."""
        self.delivered += count
        self.backlog += count
        for mid, k in self.dispatcher.assign_run(count):
            core = self.cores[mid]
            buf = core.buf
            batch = core.machine.config.batch
            while k > 0:
                take = batch - len(buf)
                if take > k:
                    take = k
                if not core.armed and core.timeout is not None:
                    core.armed = True
                    core.armed_at = now
                    push(now + core.timeout, _K_FLUSH, self.name, (mid, core.token))
                buf.extend(Instance(frame, now) for _ in range(take))
                k -= take
                if len(buf) >= batch:
                    self.close(mid, batch_ready=now, now=now, push=push)
                    buf = core.buf  # close swapped in a fresh buffer

    def close(
        self, mid: int, batch_ready: float, now: float, push: Callable,
        cause: str = "full",
    ) -> None:
        """Close ``mid``'s formation buffer (``cause``: why — ``"full"`` for
        a filled batch, ``"deadline"`` / ``"eos"`` / ``"drain"`` for partial
        flushes).  A partial flush marks its real members in the forensic
        ``flushed`` column: their service burned unfilled slots."""
        core = self.cores[mid]
        if cause != "full":
            col = self.flushed_col
            if col is not None:
                for i in core.buf:
                    if i.frame >= 0:
                        col[i.frame] = True
        if self.obs is not None:
            self.obs.batch_close(now, self.name, mid, len(core.buf), cause)
        core.close(batch_ready)
        if self.watchdog is not None:
            # detection heartbeat: the batch must complete within k x its
            # modeled service or the machine escalates suspect -> dead.
            # Armed even for a silently-crashed core — that is exactly the
            # batch whose missed heartbeat reveals the crash.
            self.watchdog(self.name, mid, core, now)
        self.start_next(mid, now, push)

    def start_next(self, mid: int, now: float, push: Callable) -> bool:
        """Start the next queued batch on ``mid`` (unless backpressured)."""
        core = self.cores[mid]
        tel = self.obs
        # the close time of the batch about to start, for the waits' split
        closed_at = core.queue[0][0] if tel is not None and core.queue else 0.0
        src, obs = self.service_time, self.service_obs
        if src is None and obs is None:
            started = core.start(now, lambda members: core.machine.config.duration)
        else:
            drawn: list[float] = []

            def _dur(members) -> float:
                d = (
                    core.machine.config.duration
                    if src is None
                    else src.duration(self.name, core.machine, len(members))
                )
                drawn.append(d)
                return d

            started = core.start(now, _dur)
        if started is None:
            return False
        end, members = started
        if obs is not None and drawn:
            obs(self.name, core.machine, drawn[0], now)
        self.stats.batches += 1
        self.backlog -= len(members)
        if self.phantoms:
            self.phantoms -= sum(1 for i in members if i.frame < 0)
        self.in_service[mid] = members
        if tel is not None:
            d = (
                drawn[0]
                if (src is not None or obs is not None) and drawn
                else core.machine.config.duration
            )
            start = end - d
            reals = [i.ready for i in members if i.frame >= 0]
            tel.batch_start(
                self.name, mid, start, d, len(members),
                core.machine.config.batch, len(members) - len(reals),
            )
            tel.waits(
                self.name, sum(closed_at - r for r in reals),
                len(reals) * (start - closed_at), len(reals) * d, len(reals),
            )
            tel.queue_depth(now, self.name, self.backlog)
        # the flag tells the owner whether this free can move a real frame
        push(end, _K_FREE, self.name, (mid, any(i.frame >= 0 for i in members)))
        return True

    def fail_machine(self, mid: int, now: float) -> "list[Instance]":
        """Declare machine ``mid`` dead and reclaim its unfinished work.

        Fences the core (`MachineCore.fail`), removes the machine from the
        dispatch walk, and returns the REAL instances the owner must
        re-queue to surviving siblings: the batch in service (reclaimed
        from ``in_service`` — its pending free event is fenced off by the
        ``failed`` flag), the closed batches queued behind it, and the
        open formation buffer.  Phantom members are simply dropped (dummy
        traffic is priced, not conserved).  The fenced core stays in
        ``cores`` so stale flush/free events die cleanly; the next plan
        hot-swap retires it.

        Bookkeeping: queued/buffered members leave the backlog here and
        re-enter it on re-delivery; ``delivered`` rolls back for every
        surrendered member so the phantom pacing anchor does not count
        the same instance twice.
        """
        core = self.cores.get(mid)
        if core is None:
            return []  # fully retired: nothing left to reclaim
        # The machine may already be out of the dispatch walk (an epoch swap
        # retired the silently-crashed core before the watchdog's verdict) —
        # its stranded members are reclaimed all the same.  Idempotence is
        # the caller's job (`FaultRuntime.dead`): a second call would find
        # the buffers already emptied and reclaim nothing, but must not
        # re-roll the bookkeeping.
        in_flight = list(self.in_service.pop(mid, ()))
        waiting = core.fail()
        members = in_flight + waiting
        self.backlog -= len(waiting)
        self.phantoms -= sum(1 for i in waiting if i.frame < 0)
        self.delivered -= len(members)
        self.machines = [m for m in self.machines if m.mid != mid]
        self.dispatcher.update(self.machines)
        return [i for i in members if i.real]

    def discard_leftover(self, mid: int) -> list[Instance]:
        """End-of-stream drop of the open buffer; returns real instances."""
        all_members = self.cores[mid].discard()
        self.backlog -= len(all_members)
        dropped = [i for i in all_members if i.real]
        self.phantoms -= len(all_members) - len(dropped)
        self.stats.dropped += len(dropped)
        return dropped


# event kinds of the pipeline's global heap (core.py re-exports): arrivals
# first (a request landing exactly at a deadline joins the batch), then
# machine-frees (upstream completions must deliver before a downstream flush
# at the same instant fires), then flushes, then control-plane epochs (a
# swap observes everything that happened up to and including its instant).
# FREE-before-FLUSH within one stage is outcome-equivalent to the
# single-module core's FLUSH-before-FREE (both orders start the same FIFO
# batch at the same time).  Faults sort last: a batch completing exactly at
# a crash instant completes, and a detection verdict at an epoch boundary
# sees the post-swap stage.
_K_ARRIVE, _K_FREE, _K_FLUSH, _K_EPOCH, _K_FAULT = 0, 1, 2, 3, 4
