"""Structured trace recorder: ring-buffered typed events, Perfetto export.

The recorder is a passive sink: the serving loops call its ``span`` /
``instant`` hooks at the points where state changes (batch starts, flush
causes, admission sheds, epoch swaps), and nothing about the simulation
reads it back — results are bit-identical with tracing on or off, which is
what lets the tracing-overhead CI gate compare the two runs directly.

Two cost controls keep the hooks cheap enough for the hot path:

* a **ring buffer** of fixed ``capacity``: the recorder never grows beyond
  it; once full, the oldest events are overwritten and counted in
  :attr:`dropped` (a long run keeps its most recent window, which is the
  one a tail-latency investigation needs);
* **sampling** for the high-frequency event classes (batch spans, parking
  instants): ``sample=0.1`` records every 10th such event via a stride
  counter — deterministic, not random, so repeated runs trace identically.
  Low-frequency control-plane events (epoch swaps, admission sheds, flush
  causes) are always recorded.

Export is the Chrome trace-event JSON format (``traceEvents`` array), which
Perfetto (https://ui.perfetto.dev) loads directly: one process per module,
one thread per machine, ``X`` complete spans for batch service, ``i``
instants for flushes / sheds / epochs, and ``C`` counters for queue depth.
"""
from __future__ import annotations

import json

# event tuple layout: (kind, ts, module, mid, name, dur, args)
#   kind 0 = span (batch service), 1 = instant, 2 = counter
_SPAN, _INSTANT, _COUNTER = 0, 1, 2

# synthetic pid for events not tied to a module (admission, control plane)
_CTRL = "(frontend/control)"


class TraceRecorder:
    """Fixed-capacity ring buffer of typed serving events."""

    __slots__ = (
        "capacity", "stride", "_buf", "_head", "dropped", "_n_hot",
    )

    def __init__(self, capacity: int = 200_000, sample: float = 1.0):
        if capacity < 1:
            raise ValueError("trace capacity must be >= 1")
        if not 0.0 < sample <= 1.0:
            raise ValueError("trace sample must be in (0, 1]")
        self.capacity = capacity
        # deterministic stride sampling: record every k-th hot event
        self.stride = max(1, round(1.0 / sample))
        self._buf: list = []
        self._head = 0
        self.dropped = 0       # events overwritten by the ring
        self._n_hot = 0        # hot-event counter driving the sample stride

    # -- recording ----------------------------------------------------------
    def _push(self, ev: tuple) -> None:
        buf = self._buf
        if len(buf) < self.capacity:
            buf.append(ev)
            return
        buf[self._head] = ev
        self._head = (self._head + 1) % self.capacity
        self.dropped += 1

    def sampled(self) -> bool:
        """Advance the hot-event stride; True when this event is recorded."""
        n = self._n_hot
        self._n_hot = n + 1
        return n % self.stride == 0

    def span(self, ts: float, dur: float, module: str, mid: int,
             name: str, **args) -> None:
        """A complete span (batch service) on module ``module``, machine
        ``mid`` — caller is responsible for sampling (see :meth:`sampled`)."""
        self._push((_SPAN, ts, module, mid, name, dur, args or None))

    def instant(self, ts: float, module: "str | None", mid: int,
                name: str, **args) -> None:
        """A point event (flush cause, shed, epoch swap, drain)."""
        self._push((_INSTANT, ts, module or _CTRL, mid, name, 0.0, args or None))

    def counter(self, ts: float, module: str, name: str, value: float) -> None:
        """A counter sample (queue depth) rendered as a track in Perfetto."""
        self._push((_COUNTER, ts, module, 0, name, 0.0, {"value": value}))

    # -- export -------------------------------------------------------------
    def events(self) -> list:
        """Buffered events in recording order (ring unwound)."""
        return self._buf[self._head:] + self._buf[:self._head]

    def to_chrome(self) -> dict:
        """The trace as a Chrome trace-event / Perfetto-loadable dict.

        One process per module (pid = first-seen order), one thread per
        machine id; timestamps converted to microseconds.
        """
        pids: dict[str, int] = {}
        out: list[dict] = []
        for kind, ts, module, mid, name, dur, args in self.events():
            pid = pids.get(module)
            if pid is None:
                pid = pids[module] = len(pids) + 1
            us = ts * 1e6
            if kind == _SPAN:
                ev = {
                    "name": name, "cat": "service", "ph": "X",
                    "ts": us, "dur": dur * 1e6, "pid": pid, "tid": mid,
                }
            elif kind == _INSTANT:
                ev = {
                    "name": name, "cat": "event", "ph": "i", "s": "t",
                    "ts": us, "pid": pid, "tid": mid,
                }
            else:  # _COUNTER
                ev = {
                    "name": name, "cat": "gauge", "ph": "C",
                    "ts": us, "pid": pid, "tid": 0, "args": args,
                }
            if args and kind != _COUNTER:
                ev["args"] = args
            out.append(ev)
        meta = []
        for module, pid in pids.items():
            meta.append({
                "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                "args": {"name": module},
            })
            meta.append({
                "name": "process_sort_index", "ph": "M", "pid": pid, "tid": 0,
                "args": {"sort_index": pid},
            })
        return {"traceEvents": meta + out, "displayTimeUnit": "ms"}

    def export(self, path: str) -> str:
        """Write the Perfetto-loadable JSON to ``path``; returns ``path``."""
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
            f.write("\n")
        return path
