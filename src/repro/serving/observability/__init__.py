"""Serving observability layer: tracing, metrics, spans, and SLO-miss forensics.

Four pieces, all passive (results are bit-identical with observability on,
off, or sampled — the layer only *watches* the simulation):

* :mod:`.trace`     — ring-buffered structured trace recorder with
  deterministic sampling; exports Chrome-trace/Perfetto JSON so a serve run
  renders as a per-machine/per-module timeline.
* :mod:`.metrics`   — cheap per-module counters and sums (batch occupancy,
  dummy fill, backpressure stalls, utilization, member waits, host-span
  seconds), flushed per control-plane epoch into ``ServeResult.metrics``.
* :mod:`.spans`     — the served path's host spans on the profiler's clock
  (``serve``, ``step``, ``dispatch``, ``sync``, ``gc``); all but ``serve``
  and ``step`` also feed the metrics registry of the run in progress.
* :mod:`.forensics` — classifies every missed/shed frame of a pipelined run
  into an exhaustive cause taxonomy with a conservation invariant; no
  opt-in needed (its columns are always on).

Enable via ``ServingEngine.run(..., observability=True)`` (or an
:class:`ObservabilityConfig`); dump with ``launch/serve.py --trace``.  The
:class:`Observability` runtime is the single object the serving loops talk
to: every hook guards on the piece being enabled, and the loops guard on
the runtime being present at all, so the disabled path stays hook-free.

Fields of a module's metrics row beside the batch counts (all sums, so rows
add up): ``collect_s`` — Σ over the real members of its started batches of
(batch close − member ready); ``queue_s`` — Σ (batch start − batch close);
``service_s`` — Σ (batch end − batch start); ``waited`` — those members.
All four are in the loop's clock, on both paths (event loop, segment fast
path).  With real executors (``launch/serve.py --real``) a row
also carries ``dispatch_s`` / ``dispatch_n`` / ``dispatch_max_s`` (host
time enqueueing the compiled forward; ``dispatch_n`` is the call count),
and the same three for ``sync`` (the wait in ``block_until_ready``).  The
``serve`` and ``step`` spans annotate the profiler trace only.  The
``(host)`` row carries ``gc_s`` / ``gc_n`` / ``gc_max_s``: garbage
collections while the run was in progress.

The spans themselves land in a profiler trace taken around a real run, e.g.
``jax.profiler.start_trace(dir)`` before and ``stop_trace()`` after
``repro.launch.serve.main(["--arch", "smollm-360m", "--real",
"--trace"])``, and show on the trace's host plane next to the
device's ops (TensorBoard's profile plugin or Perfetto read the
``.xplane.pb``).  Each module's forward is jitted under its own name and
``jax.named_scope``, so the device's XLA Modules line names it.
"""
from __future__ import annotations

from dataclasses import dataclass

from .forensics import MISS_CAUSES, MissReport, classify_misses
from .metrics import MetricsRegistry, MetricsSnapshot
from .spans import active, annotate, span
from .trace import TraceRecorder


@dataclass(frozen=True)
class ObservabilityConfig:
    """Engine-facing knobs for ``ServingEngine.run(..., observability=...)``.

    ``trace`` / ``metrics`` toggle the two recorders independently;
    ``sample`` thins the high-frequency trace events (batch spans, parking)
    by a deterministic stride (0.1 = every 10th), control-plane events are
    always recorded; ``capacity`` bounds the trace ring buffer.
    """

    trace: bool = True
    metrics: bool = True
    sample: float = 1.0
    capacity: int = 200_000

    def __post_init__(self):
        if not 0.0 < self.sample <= 1.0:
            raise ValueError("sample must be in (0, 1]")
        if self.capacity < 1:
            raise ValueError("capacity must be >= 1")


class Observability:
    """The live hook sink threaded through the serving loops."""

    __slots__ = ("cfg", "trace", "metrics")

    def __init__(self, cfg: ObservabilityConfig):
        self.cfg = cfg
        self.trace = (
            TraceRecorder(cfg.capacity, cfg.sample) if cfg.trace else None
        )
        self.metrics = MetricsRegistry() if cfg.metrics else None

    @staticmethod
    def make(spec) -> "Observability | None":
        """Resolve the engine's ``observability=`` argument (None / False /
        True / ObservabilityConfig / Observability)."""
        if spec is None or spec is False:
            return None
        if isinstance(spec, Observability):
            return spec
        if spec is True:
            spec = ObservabilityConfig()
        if not isinstance(spec, ObservabilityConfig):
            raise TypeError(
                f"observability= expects bool or ObservabilityConfig, got {spec!r}"
            )
        return Observability(spec)

    # -- hot-path hooks (loops guard on the runtime being non-None) ---------
    def batch_start(self, module: str, mid: int, start: float, dur: float,
                    size: int, cap: int, n_phantom: int) -> None:
        """A batch began service on ``module``/``mid`` at ``start``."""
        if self.metrics is not None:
            self.metrics.batch(module, size, cap, n_phantom, dur)
        tr = self.trace
        if tr is not None and tr.sampled():
            tr.span(
                start, dur, module, mid, f"batch b={size}/{cap}",
                phantoms=n_phantom,
            )

    def batch_close(self, t: float, module: str, mid: int, size: int,
                    cause: str) -> None:
        """A formation buffer closed (``cause``: full/deadline/eos/drain)."""
        if self.metrics is not None:
            self.metrics.close(module, cause)
        tr = self.trace
        if tr is not None and cause != "full":
            # partial flushes are the interesting (and rare) closes; full
            # closes are implied by the batch spans
            tr.instant(t, module, mid, f"flush:{cause}", size=size)

    def waits(self, module: str, collect: float, queue: float,
              service: float, waited: int) -> None:
        """Waits of ``waited`` real members of started batches (sums; see
        `MetricsRegistry.waits`): per batch on the event paths, per module
        replay on the column paths."""
        if self.metrics is not None:
            self.metrics.waits(module, collect, queue, service, waited)

    def park(self, t: float, module: str) -> None:
        """A delivery parked under backpressure."""
        if self.metrics is not None:
            self.metrics.park(module)
        tr = self.trace
        if tr is not None and tr.sampled():
            tr.instant(t, module, 0, "park")

    def queue_depth(self, t: float, module: str, depth: int) -> None:
        tr = self.trace
        if tr is not None and tr.sampled():
            tr.counter(t, module, "queue_depth", depth)

    def shed(self, t: float, kind: str) -> None:
        """An admission decision denied a frame.

        ``kind``: ``"shed"`` (terminal), ``"shed_retry"`` (interim
        closed-loop denial the client re-issues), or ``"pipeline_drop"``
        (an in-flight instance drop lost the frame).  Summing ``"shed"``
        instants over a run equals terminal ``ServeResult.shed``.
        """
        if self.metrics is not None:
            self.metrics.close("(ingress)", kind)
        if self.trace is not None:
            self.trace.instant(t, None, 0, kind)

    def drain(self, t: float, module: str, mid: int) -> None:
        """A machine was marked draining by a plan hot-swap."""
        if self.trace is not None:
            self.trace.instant(t, module, mid, "drain")

    # -- failure lifecycle hooks (always recorded, like control events) ------
    def suspect(self, t: float, module: str, mid: int) -> None:
        """The watchdog missed a heartbeat: machine flagged suspect."""
        if self.trace is not None:
            self.trace.instant(t, module, mid, "suspect")

    def fail(self, t: float, module: str, mid: int) -> None:
        """A machine was declared dead (second missed heartbeat)."""
        if self.metrics is not None:
            self.metrics.close(module, "machine_dead")
        if self.trace is not None:
            self.trace.instant(t, module, mid, "fail")

    def requeue(self, t: float, module: str, mid: int, n: int) -> None:
        """``n`` unfinished members of a dead machine re-queued to siblings."""
        if self.trace is not None:
            self.trace.instant(t, module, mid, "requeue", members=n)

    def promote_spare(self, t: float, module: str, mid: int) -> None:
        """A warm spare was promoted back into dispatch by a stage update."""
        if self.trace is not None:
            self.trace.instant(t, module, mid, "promote_spare")

    # -- multi-tenant pool hooks (always recorded, like control events) -----
    def colocate(self, t: float, did: int, app: str, module: str, mid: int,
                 fraction: float) -> None:
        """The allocator packed a module residue onto shared device ``did``."""
        if self.trace is not None:
            self.trace.instant(
                t, "(pool)", did, "colocate",
                app=app, stage=module, machine=mid, frac=round(fraction, 4),
            )

    def evict(self, t: float, did: int, app: str, module: str,
              mid: int) -> None:
        """A repack removed a residue from its shared device ``did``."""
        if self.trace is not None:
            self.trace.instant(
                t, "(pool)", did, "evict", app=app, stage=module, machine=mid,
            )

    def device_occupancy(self, t: float, did: int, occupancy: float) -> None:
        """Per-device occupancy sample after a (re)pack."""
        if self.trace is not None:
            self.trace.counter(
                t, "(pool)", f"dev{did}_occupancy", round(occupancy, 4)
            )

    def phantom(self, t: float, module: str) -> None:
        """An adaptive phantom was injected into ``module``'s formation."""
        tr = self.trace
        if tr is not None and tr.sampled():
            tr.instant(t, module, 0, "phantom")

    def epoch(self, t: float, record, machines_of: "dict[str, int]") -> None:
        """A control-plane epoch boundary fired (after same-instant events)."""
        if self.metrics is not None:
            self.metrics.flush(t, machines_of, record.duration_err)
        if self.trace is not None:
            self.trace.instant(
                t, None, 0, "epoch",
                version=record.version,
                target=round(record.target, 3),
                swapped=record.swapped,
                delta=record.delta_summary,
            )

    # -- column-level hooks (segment fast path) ------------------------------
    def bulk_module(self, module: str, *, batches: int, members: int,
                    phantoms: int, slots: int, busy: float) -> None:
        if self.metrics is not None:
            self.metrics.bulk(
                module, batches=batches, members=members, phantoms=phantoms,
                slots=slots, busy=busy,
            )

    def finalize(self, t_end: float,
                 machines_of: "dict[str, int]") -> "MetricsSnapshot | None":
        """Flush the trailing accumulation window; returns the snapshot."""
        if self.metrics is None:
            return None
        self.metrics.flush(t_end, machines_of)
        return self.metrics.snapshot()


__all__ = [
    "MISS_CAUSES",
    "MetricsRegistry",
    "MetricsSnapshot",
    "MissReport",
    "Observability",
    "ObservabilityConfig",
    "TraceRecorder",
    "active",
    "annotate",
    "classify_misses",
    "span",
]
