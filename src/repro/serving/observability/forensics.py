"""SLO-miss forensics: classify every missed or shed frame into one cause.

A serve run's attainment number says *how many* frames missed the SLO; this
module says *why*, per frame, from the per-frame columns the pipelined
co-simulation already records (`pipeline.result.FrameTable`) plus the
control plane's epoch audit trail.  The taxonomy, in classification
priority order (each frame gets exactly ONE cause — the first that
applies):

============================ ===============================================
``admission_shed``           rejected at ingress by the admission policy
``machine_failure``          lost outright to a machine declared dead: its
                             in-flight work died with the machine and no
                             surviving sibling completed it
``recovery_transient``       late frame whose in-flight work was re-queued
                             off a dead machine — it completed, but paid
                             the detection latency + the re-queue wait
``admission_drop``           admitted, then lost mid-pipeline (tail drop,
                             zero-completion stage)
``cold_start_epoch``         late frame issued before the control plane's
                             first replan landed (the warm-up window a
                             misprovisioned initial plan has not yet been
                             repaired in)
``under_provisioned_epoch``  late frame issued in an epoch whose realized
                             offered rate exceeded the plan's provisioned
                             target — the estimator lagged the ramp
``backpressure_stall``       late frame that was parked by a bounded-queue
                             stage (cross-stage interference)
``flush_waste``              late frame served out of a deadline/drain/EOS
                             partial batch — capacity burned on unfilled
                             slots
``fanout_tail``              late frame whose critical-path-dominant stage
                             served it with fanout > 1 — its e2e waits on
                             the max over sibling instances
``service_overrun``          late frame with none of the above: plain
                             queueing + service beyond the budget
============================ ===============================================

The cascade is exhaustive by construction (``service_overrun`` absorbs the
remainder), which yields the **conservation invariant** every consumer can
assert:  ``sum(counts.values()) == misses == offered − completed-in-SLO``.

The columns feeding the middle rows (``stalled`` / ``flushed`` / ``fan``)
are always-on and cheap (one boolean/int write at an event that already
touches the frame), so forensics needs no opt-in: every served result can
answer ``miss_report()``.  One honest limitation: the segment
fast path never deadline-flushes (it only runs when the whole stream is
quiescent), so ``flushed`` stays ``False`` there and a would-be
``flush_waste`` frame classifies as ``service_overrun`` — conservation is
unaffected.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# classification priority order — index == cause code in ``cause_of``
MISS_CAUSES = (
    "admission_shed",
    "machine_failure",
    "recovery_transient",
    "admission_drop",
    "cold_start_epoch",
    "under_provisioned_epoch",
    "backpressure_stall",
    "flush_waste",
    "fanout_tail",
    "service_overrun",
)
_CODE = {c: i for i, c in enumerate(MISS_CAUSES)}


@dataclass
class MissReport:
    """Per-frame miss causes + the conservation bookkeeping around them."""

    cause_of: np.ndarray       # int8 per frame: MISS_CAUSES index, -1 = not a miss
    counts: dict[str, int]     # cause -> frame count (only the misses)
    offered: int               # completed + shed + dropped frames
    completed_in_slo: int      # completed frames with e2e <= slo
    slo: float

    @property
    def total(self) -> int:
        return int((self.cause_of >= 0).sum())

    @property
    def conserved(self) -> bool:
        """The invariant: cause counts sum exactly to total misses, and
        total misses equal offered − completed-in-SLO."""
        s = sum(self.counts.values())
        return s == self.total == self.offered - self.completed_in_slo

    @property
    def dominant(self) -> "str | None":
        """The most frequent miss cause (None when nothing missed)."""
        if not self.counts:
            return None
        return max(self.counts.items(), key=lambda kv: (kv[1], kv[0]))[0]

    def table(self) -> str:
        """Aligned text breakdown (``serve.py --trace`` / notebooks)."""
        total = self.total
        lines = [
            f"miss forensics: {total} misses / {self.offered} offered "
            f"(slo={self.slo:g}s, conserved={self.conserved})"
        ]
        for cause in MISS_CAUSES:
            n = self.counts.get(cause, 0)
            if n == 0:
                continue
            pct = 100.0 * n / max(total, 1)
            lines.append(f"  {cause:<24} {n:>8}  {pct:5.1f}%")
        return "\n".join(lines)


def classify_misses(pr, slo: float, epochs=None) -> MissReport:
    """Classify every miss of a `PipelineResult` (see module docstring).

    ``epochs`` is the control plane's ``ServeResult.epochs`` audit trail
    (or None when no control loop ran): it supplies the cold-start window
    and each epoch's provisioned target for the two epoch-level causes.
    """
    n = pr.e2e.size
    completed = pr.completed
    late = completed & (pr.e2e > slo + 1e-9)
    miss = pr.shed | pr.dropped | late
    offered = int(completed.sum() + pr.shed.sum() + pr.dropped.sum())
    in_slo = int((completed & ~late).sum())

    cause = np.full(n, -1, dtype=np.int8)

    def assign(mask: np.ndarray, name: str) -> None:
        take = miss & (cause < 0) & mask
        cause[take] = _CODE[name]

    assign(pr.shed, "admission_shed")
    # failure attribution trumps epoch attribution: a frame touched by a
    # dead machine missed because of the failure, whatever epoch it hit
    # (`failed` is None on pre-fault result objects — old pickles/tests)
    failed = getattr(pr, "failed", None)
    if failed is not None:
        assign(pr.dropped & failed, "machine_failure")
        assign(late & failed, "recovery_transient")
    assign(pr.dropped, "admission_drop")

    if epochs:
        ts = np.asarray([e.t for e in epochs], dtype=np.float64)
        issued = ~np.isnan(pr.issue)
        if ts.size >= 2:
            # cold start: issued before the first replan repaired the
            # initial plan (epochs[0] is the t=0 seed record)
            assign(late & issued & (pr.issue < ts[1]), "cold_start_epoch")
        # realized offered rate per epoch vs its provisioned target
        idx = np.searchsorted(ts, pr.issue[issued], side="right") - 1
        idx = np.clip(idx, 0, ts.size - 1)
        per_epoch = np.bincount(idx, minlength=ts.size).astype(np.float64)
        horizon = max(float(np.nanmax(pr.issue)), float(ts[-1]))
        spans = np.diff(np.append(ts, max(horizon, ts[-1] + 1e-12)))
        realized = per_epoch / np.maximum(spans, 1e-12)
        targets = np.asarray([e.target for e in epochs], dtype=np.float64)
        under = realized > targets * (1.0 + 1e-9)
        frame_epoch = np.zeros(n, dtype=np.int64)
        frame_epoch[issued] = idx
        assign(late & issued & under[frame_epoch], "under_provisioned_epoch")

    stalled = getattr(pr, "stalled", None)
    if stalled is not None:
        assign(late & stalled, "backpressure_stall")
    flushed = getattr(pr, "flushed", None)
    if flushed is not None:
        assign(late & flushed, "flush_waste")

    fan = getattr(pr, "fan", None)
    if fan is not None and late.any() and (cause[late] < 0).any():
        # dominant critical-path stage of each late frame: the one whose
        # sojourn the e2e decomposition charges the most to
        _, masks = pr.critical_path()
        soj = np.full((len(pr.modules), n), -np.inf)
        fans = np.zeros((len(pr.modules), n), dtype=np.int64)
        for i, m in enumerate(pr.modules):
            s = pr.sojourn(m)
            on = masks[m] & ~np.isnan(s)
            soj[i, on] = s[on]
            fans[i] = fan[m]
        dom = soj.argmax(axis=0)
        dom_fan = fans[dom, np.arange(n)]
        assign(late & (dom_fan > 1), "fanout_tail")

    assign(late, "service_overrun")  # exhaustive fallback

    codes, freq = np.unique(cause[cause >= 0], return_counts=True)
    counts = {MISS_CAUSES[c]: int(k) for c, k in zip(codes, freq)}
    return MissReport(
        cause_of=cause,
        counts=counts,
        offered=offered,
        completed_in_slo=in_slo,
        slo=slo,
    )
