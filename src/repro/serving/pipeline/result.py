"""Per-frame results of the pipelined co-simulation + overrun attribution.

A per-module replay only reports per-instance module latencies and a
per-frame end-to-end number; the pipelined core tracks every frame as an
entity, so this result object can answer the question the latency splitter
(`core.splitter`) actually poses: *which module's budget did a late frame
blow, and by how much?*

Attribution is exact, not heuristic.  For frame *f* define the per-module
sojourn ``s_m = finish_m - avail_m`` where ``avail_m`` is the instant every
parent finished (so queueing delay — including backpressure parking — counts
against the stage that queued).  The realized end-to-end latency decomposes
over the frame's critical path through the SP tree
(`core.dag.sp_critical_masks`), giving the identity::

    e2e(f) == sum_{m on path(f)} s_m(f)
    e2e(f) - sum_{m on path(f)} budget_m == sum_{m on path(f)} (s_m - budget_m)

so per-module overrun attributions sum to the frame's end-to-end overrun
beyond its critical-path budget sum (negative attribution = the module ran
under budget and donated slack).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Mapping, Sequence

import numpy as np

from ...core.dag import SP, sp_critical_masks
from .stages import StageStats


class FrameTable:
    """Preallocated struct-of-arrays per-frame state, indexed by frame id.

    One numpy column per fact the co-simulation tracks about a frame —
    issue/shed/lost flags, per-stage availability / finish timestamps,
    outstanding fanout counts (``pend``), parent join counters — shared by
    the event-by-event loop (which mutates single cells as events fire) and
    the segment fast-path (which fills whole columns vectorized).  Keeping
    every record columnar is what lets both producers :meth:`finalize` into
    the same :class:`PipelineResult` with one vectorized classification
    pass, and what keeps the result object O(arrays), not O(frames) Python
    objects, at 10^5+ frames.
    """

    __slots__ = (
        "n", "topo", "issue", "shed", "lost", "resolved", "sink_bad",
        "sink_max", "sinks_left", "e2e", "avail", "finish", "pend",
        "parents_left", "child_void", "child_avail", "stalled", "flushed",
        "fan", "failed",
    )

    def __init__(
        self,
        n_frames: int,
        topo: Sequence[str],
        parents: Mapping[str, Sequence[str]],
        n_sinks: int,
    ):
        n = n_frames
        self.n = n
        self.topo = tuple(topo)
        self.issue = np.full(n, np.nan)
        self.shed = np.zeros(n, dtype=bool)
        self.lost = np.zeros(n, dtype=bool)      # materialized instances, none done
        self.resolved = np.zeros(n, dtype=bool)
        self.sink_bad = np.zeros(n, dtype=bool)  # some sink never completed
        self.sink_max = np.zeros(n)
        self.sinks_left = np.full(n, n_sinks, dtype=np.int64)
        self.e2e = np.full(n, np.nan)
        self.avail = {m: np.full(n, np.nan) for m in topo}
        self.finish = {m: np.full(n, np.nan) for m in topo}
        self.pend = {m: np.zeros(n, dtype=np.int64) for m in topo}
        self.parents_left = {
            m: np.full(n, len(parents[m]), dtype=np.int64) for m in topo
        }
        self.child_void = {m: np.zeros(n, dtype=bool) for m in topo}
        self.child_avail = {m: np.zeros(n) for m in topo}
        # always-on forensic columns (`observability.forensics`): set at
        # events that already touch the frame, so they cost one cell write
        self.stalled = np.zeros(n, dtype=bool)   # parked by backpressure
        self.flushed = np.zeros(n, dtype=bool)   # served from a partial batch
        self.fan = {m: np.zeros(n, dtype=np.int64) for m in topo}
        self.failed = np.zeros(n, dtype=bool)    # touched by a machine failure

    def finalize(self, dag, stats: dict, attempts: int) -> "PipelineResult":
        """Classify every frame and assemble the result (one vector pass).

        Frames still unresolved at end of run are wedged in-pipeline: never
        issued -> shed, otherwise lost (their sinks can never complete).
        """
        un = ~self.resolved
        if un.any():
            never_issued = un & np.isnan(self.issue)
            self.shed |= never_issued
            wedged = un & ~never_issued
            self.lost |= wedged
            self.sink_bad |= wedged
        completed = ~np.isnan(self.e2e)
        dropped = self.lost & ~self.shed & ~completed
        skipped = ~completed & ~self.shed & ~dropped
        return PipelineResult(
            modules=self.topo,
            sp=dag.sp,
            issue=self.issue,
            e2e=self.e2e,
            avail=self.avail,
            finish=self.finish,
            shed=self.shed,
            dropped=dropped,
            skipped=skipped,
            stats=stats,
            attempts=attempts,
            stalled=self.stalled,
            flushed=self.flushed,
            fan=self.fan,
            failed=self.failed,
        )


@dataclass
class PipelineResult:
    """Everything the co-simulation learned about every frame."""

    modules: tuple[str, ...]
    sp: SP
    issue: np.ndarray                 # frame issue/arrival time (NaN: never issued)
    e2e: np.ndarray                   # end-to-end latency (NaN: shed/skipped/dropped)
    avail: dict[str, np.ndarray]      # per-stage availability (all parents done)
    finish: dict[str, np.ndarray]     # per-stage completion (last instance's batch)
    shed: np.ndarray                  # bool: rejected at ingress for good
    dropped: np.ndarray               # bool: admitted but lost mid-pipeline
    skipped: np.ndarray               # bool: excluded by a zero-instance fanout
    stats: dict[str, StageStats]
    attempts: int = 0                 # closed-loop issue attempts (0 = open loop)
    # forensic columns (see `observability.forensics`): parked under
    # backpressure, served from a partial (deadline/drain/EOS) batch, and
    # per-module realized fanout counts
    stalled: "np.ndarray | None" = None
    flushed: "np.ndarray | None" = None
    fan: "dict[str, np.ndarray] | None" = None
    # frames whose in-flight work was on a machine later declared dead
    # (re-queued to siblings, or lost when none survived)
    failed: "np.ndarray | None" = None
    _path_cache: "tuple[np.ndarray, dict[str, np.ndarray]] | None" = field(
        default=None, repr=False, compare=False
    )

    @property
    def completed(self) -> np.ndarray:
        return ~np.isnan(self.e2e)

    def sojourn(self, m: str) -> np.ndarray:
        """Per-frame time spent at module ``m`` (queueing + collection +
        service + backpressure parking), NaN where never traversed."""
        return self.finish[m] - self.avail[m]

    def critical_path(self) -> tuple[np.ndarray, dict[str, np.ndarray]]:
        """``(path_latency, masks)`` — see `core.dag.sp_critical_masks`."""
        if self._path_cache is None:
            sojourns = {m: self.sojourn(m) for m in self.modules}
            self._path_cache = sp_critical_masks(self.sp, sojourns)
        return self._path_cache

    def overrun_attribution(
        self, budgets: Mapping[str, float]
    ) -> tuple[dict[str, np.ndarray], np.ndarray]:
        """Per-frame, per-module budget-overrun attribution.

        Returns ``(attr, path_budget)``: ``attr[m][f]`` is frame *f*'s
        overrun charged to module *m* (``s_m - budget_m`` on the critical
        path, 0 off it) and ``path_budget[f]`` the budget sum along the
        frame's realized critical path.  Exact identity (completed frames)::

            sum_m attr[m][f] == e2e[f] - path_budget[f]
        """
        _, masks = self.critical_path()
        attr: dict[str, np.ndarray] = {}
        path_budget = np.zeros(self.e2e.size)
        for m in self.modules:
            on = masks[m]
            attr[m] = np.where(on, self.sojourn(m) - budgets[m], 0.0)
            path_budget += np.where(on, budgets[m], 0.0)
        return attr, path_budget

    def overrun_by_module(
        self, budgets: Mapping[str, float], slo: float
    ) -> dict[str, float]:
        """Mean attributed overrun per module across SLO-missing frames —
        the one-line answer to 'which budget assignment is wrong'."""
        late = self.completed & (self.e2e > slo + 1e-9)
        if not late.any():
            return {m: 0.0 for m in self.modules}
        attr, _ = self.overrun_attribution(budgets)
        return {m: float(attr[m][late].mean()) for m in self.modules}

    def miss_report(self, slo: float, epochs=None):
        """Classify every missed/shed frame into exactly one cause (an
        `observability.forensics.MissReport`, conservation-checked —
        ``epochs`` is the control plane's audit trail when one ran)."""
        from ..observability.forensics import classify_misses

        return classify_misses(self, slo, epochs)
