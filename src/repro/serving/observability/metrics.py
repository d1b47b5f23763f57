"""Low-overhead metrics registry: per-module counters and sums.

The registry accumulates cheap scalar state per module while the serving
loop runs — integer counters (batches, close causes, backpressure parks),
running sums for means (batch occupancy, dummy fill), a busy-time
integrator for utilization, the waits of each batch's real members
(collection and queueing, in the loop's clock), and the seconds of the
served path's host spans (`.spans`: executor dispatch and sync, garbage
collections).  At every control-plane epoch boundary (and once at end of
run) the accumulators flush into one row per module per epoch; the rows
travel on ``ServeResult.metrics`` as a :class:`MetricsSnapshot`.  Rows
carry raw sums and counts, never only means, so a reader can sum them over
rows.

Everything here is plain Python arithmetic on a handful of attributes — no
numpy allocation per event — so the registry stays inside the tracing
overhead budget (the ``pipeline_speed`` smoke gate's <= 10%).
"""
from __future__ import annotations

from dataclasses import dataclass, field


class _ModuleAcc:
    """One module's accumulators between two epoch flushes."""

    __slots__ = (
        "batches", "members", "phantoms", "slots", "parks", "busy",
        "closes", "collect", "queue", "service", "waited", "spans",
    )

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.batches = 0       # batches started
        self.members = 0       # members (real + phantom) across started batches
        self.phantoms = 0      # phantom members across started batches
        self.slots = 0         # capacity slots across started batches
        self.parks = 0         # deliveries parked by backpressure
        self.busy = 0.0        # seconds of machine service time
        self.closes = {}       # close cause -> count
        self.collect = 0.0     # sum over real members: batch close - member ready
        self.queue = 0.0       # sum over real members: batch start - batch close
        self.service = 0.0     # sum over real members: batch end - batch start
        self.waited = 0        # real members behind the three sums
        self.spans = {}        # span kind -> [seconds, count, longest]

    @property
    def empty(self) -> bool:
        return (
            self.batches == 0 and self.parks == 0 and not self.closes
            and not self.spans
        )


@dataclass
class MetricsSnapshot:
    """Flushed per-module-per-epoch metric rows (``ServeResult.metrics``)."""

    rows: list[dict] = field(default_factory=list)

    def for_module(self, module: str) -> list[dict]:
        return [r for r in self.rows if r["module"] == module]

    def table(self) -> str:
        """Aligned text table of the per-epoch rows (``serve.py --trace``).

        ``collect_ms`` / ``queue_ms`` are the mean collection and queueing
        wait per real member of the row's started batches."""
        cols = (
            "epoch", "module", "t0", "t1", "batches", "occupancy",
            "dummy_fill", "stalls", "utilization", "duration_err",
            "collect_ms", "queue_ms",
        )
        lines = ["  ".join(f"{c:>12}" for c in cols)]
        for r in self.rows:
            n = max(r.get("waited", 0), 1)
            derived = {
                "collect_ms": 1e3 * r.get("collect_s", 0.0) / n,
                "queue_ms": 1e3 * r.get("queue_s", 0.0) / n,
            }
            cells = []
            for c in cols:
                v = derived[c] if c in derived else r.get(c, 0.0)
                cells.append(
                    f"{v:>12.4f}" if isinstance(v, float) else f"{v:>12}"
                )
            lines.append("  ".join(cells))
        return "\n".join(lines)


class MetricsRegistry:
    """Accumulate per-module counters; flush one row per module per epoch."""

    __slots__ = ("_acc", "rows", "_t0", "_epoch")

    def __init__(self):
        self._acc: dict[str, _ModuleAcc] = {}
        self.rows: list[dict] = []
        self._t0 = 0.0
        self._epoch = 0

    def _mod(self, module: str) -> _ModuleAcc:
        acc = self._acc.get(module)
        if acc is None:
            acc = self._acc[module] = _ModuleAcc()
        return acc

    # -- hot-path accumulation ----------------------------------------------
    def batch(self, module: str, size: int, cap: int, n_phantom: int,
              dur: float) -> None:
        acc = self._mod(module)
        acc.batches += 1
        acc.members += size
        acc.phantoms += n_phantom
        acc.slots += cap
        acc.busy += dur

    def close(self, module: str, cause: str) -> None:
        acc = self._mod(module)
        acc.closes[cause] = acc.closes.get(cause, 0) + 1

    def park(self, module: str) -> None:
        self._mod(module).parks += 1

    def waits(self, module: str, collect: float, queue: float,
              service: float, waited: int) -> None:
        """Fold the waits of ``waited`` real members of started batches:
        Σ (batch close − member ready), Σ (batch start − batch close) and
        Σ (batch end − batch start), all in the loop's clock.  One batch at
        a time on the event paths, one module replay at a time on the
        column paths."""
        acc = self._mod(module)
        acc.collect += float(collect)
        acc.queue += float(queue)
        acc.service += float(service)
        acc.waited += waited

    def span(self, module: str, kind: str, seconds: float, n: int = 1) -> None:
        """``n`` host spans of ``kind`` (``dispatch``, ``sync``, ``gc``...)
        that took ``seconds`` of wall time on behalf of ``module`` (``n=0``
        puts the kind on the row at zero before any span of it ends)."""
        acc = self._mod(module)
        tot = acc.spans.get(kind)
        if tot is None:
            acc.spans[kind] = [seconds, n, seconds]
        else:
            tot[0] += seconds
            tot[1] += n
            if seconds > tot[2]:
                tot[2] = seconds

    # -- column-level accumulation (segment fast path) -----------------------
    def bulk(self, module: str, *, batches: int, members: int,
             phantoms: int, slots: int, busy: float) -> None:
        """Fold one vectorized module replay's aggregate into the epoch."""
        acc = self._mod(module)
        acc.batches += batches
        acc.members += members
        acc.phantoms += phantoms
        acc.slots += slots
        acc.busy += busy
        if batches:
            acc.closes["full"] = acc.closes.get("full", 0) + batches

    # -- epoch flush --------------------------------------------------------
    def flush(self, t1: float, machines_of: "dict[str, int]",
              duration_err: float = 0.0) -> None:
        """Close the accumulation window ``[t0, t1)`` into one row per
        module; ``machines_of`` maps module -> active machine count (the
        utilization denominator)."""
        span = max(t1 - self._t0, 0.0)
        for module, acc in sorted(self._acc.items()):
            if acc.empty:
                continue
            n_m = max(machines_of.get(module, 1), 1)
            members = max(acc.members, 1)
            row = {
                "epoch": self._epoch,
                "module": module,
                "t0": self._t0,
                "t1": t1,
                "batches": acc.batches,
                "occupancy": acc.members / max(acc.slots, 1),
                "dummy_fill": acc.phantoms / members,
                "stalls": acc.parks,
                "utilization": (
                    acc.busy / (n_m * span) if span > 0.0 else 0.0
                ),
                "duration_err": duration_err,
                "closes": dict(acc.closes),
                "collect_s": acc.collect,
                "queue_s": acc.queue,
                "service_s": acc.service,
                "waited": acc.waited,
            }
            for kind, (total, n, longest) in acc.spans.items():
                row[f"{kind}_s"] = total
                row[f"{kind}_n"] = n
                row[f"{kind}_max_s"] = longest
            self.rows.append(row)
            acc.reset()
        self._t0 = t1
        self._epoch += 1

    def snapshot(self) -> MetricsSnapshot:
        return MetricsSnapshot(rows=self.rows)
