"""Reduce a profiler trace (``.xplane.pb``) to the numbers the readers use.

Read with ``jax.profiler.ProfileData`` alone.  A device plane is one whose
name starts with ``/device:TPU:``; its operations are the events of its
``XLA Ops`` line, each named by its HLO instruction (``%flash_attention.6 =
bf16[...] custom-call(...)``: the op's own name is the part before `` = ``,
and a Pallas kernel's is its jitted wrapper's, ``flash_attention`` or
``fused_rmsnorm``, with a numeric suffix).  Per device:

* busy seconds: the union of the operations' intervals (averaged over the
  devices traced);
* a kernel's seconds and calls: the operations whose own name, without the
  ``%`` and the numeric suffix, is the kernel's;
* the device operations that took most time, by own name, leaving out the
  loops (``while``) that hold other operations;
* the idle gaps: the stretches between busy intervals, each named after the
  host span that was open at its middle (``executor <module> b<batch>``, the
  harness's annotation of each executor call), or ``serving loop`` where
  none was.

``window_s`` is the traced chunk's wall time on the host's clock.
"""
from __future__ import annotations

from collections import Counter
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_SPAN = "executor "
CONTAINERS = ("while", "conditional", "call")


def own_name(event_name: str) -> str:
    """``%flash_attention.6 = bf16[...] ...`` -> ``flash_attention.6``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def kind_of(name: str) -> str:
    """``flash_attention.6`` -> ``flash_attention``."""
    base, _, suffix = name.rpartition(".")
    return base if base and suffix.isdigit() else name


@dataclass
class Op:
    name: str  # own name, e.g. ``fusion.108``
    start_ns: float
    dur_ns: float


@dataclass
class Summary:
    window_s: float
    busy_s: float
    ops: list[Op] = field(default_factory=list)      # of the first device
    gaps: list[tuple[str, float]] = field(default_factory=list)
    n_devices: int = 1

    def kernel(self, name: str) -> tuple[float, int]:
        """Seconds and number of the operations of kernel ``name``."""
        hits = [o for o in self.ops if kind_of(o.name) == name]
        return sum(o.dur_ns for o in hits) / 1e9, len(hits)

    def breakdown(self) -> dict:
        tot: Counter = Counter()
        for o in self.ops:
            if kind_of(o.name) not in CONTAINERS:
                tot[o.name] += o.dur_ns
        ops = [[n, d / 1e9] for n, d in tot.most_common(10)]
        gaps = [[n, s] for n, s in sorted(self.gaps, key=lambda g: -g[1])[:10]]
        return {"device_ops": ops, "idle_gaps": gaps}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def reduce_file(path: Path, *, window_s: float) -> Summary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [Op(own_name(e.name), e.start_ns, e.duration_ns) for e in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    devices = [d for d in devices if d]
    if not devices:
        return Summary(window_s=window_s, busy_s=0.0)
    busy = []
    for ops in devices:
        u = _union([(o.start_ns, o.start_ns + o.dur_ns) for o in ops])
        busy.append(sum(e - s for s, e in u) / 1e9)
    first = _union([(o.start_ns, o.start_ns + o.dur_ns) for o in devices[0]])
    # name only the longest gaps: the breakdown keeps ten
    between = sorted(zip(first, first[1:]), key=lambda p: p[0][1] - p[1][0])[:10]
    gaps = []
    for (_, e0), (s1, _) in between:
        mid = 0.5 * (e0 + s1)
        name = next((n for s, e, n in spans if s <= mid <= e), "serving loop")
        gaps.append((name, (s1 - e0) / 1e9))
    return Summary(
        window_s=window_s,
        busy_s=sum(busy) / len(busy),
        ops=devices[0],
        gaps=gaps,
        n_devices=len(devices),
    )


def reduce_dir(trace_dir: Path, *, window_s: float) -> Summary:
    """Reduce the one ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``trace_dir``."""
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {len(found)}")
    return reduce_file(found[0], window_s=window_s)
