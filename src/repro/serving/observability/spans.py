"""Spans of the served path on the profiler's clock, and the runtime they feed.

:class:`span` times the executor calls of the served path (the
``dispatch`` and ``sync`` of ``repro.launch.serve.ModuleExecutor``).  A span
does two things:

* it opens a ``jax.profiler.TraceAnnotation`` named
  ``"<kind> <module> b<batch>"``, so it lands on the host plane of any
  profiler trace, on the same timeline as the device's operations;
* while an :class:`~repro.serving.observability.Observability` runtime with
  metrics is :func:`active` (``ServingEngine.run`` sets it for its
  duration), it adds its wall seconds to that runtime's
  ``MetricsRegistry`` under ``kind`` (the row fields ``<kind>_s``,
  ``<kind>_n`` and ``<kind>_max_s``).

:func:`annotate` opens the same annotation and feeds no counter: the root
``serve`` (``ServingEngine.run``) and the ``step <module> b<batch>`` of
``LiveServiceTime.duration``, a ``StepTraceAnnotation`` numbered by the
calls made, so the profiler's step view groups each batch's device ops.

A process that has not imported JAX has no profiler, so a span there only
feeds the registry; nothing here imports JAX.  With no runtime active a span
costs building its annotation and touches no counter.

While a runtime is active, a ``gc.callbacks`` hook spans every garbage
collection as ``gc`` (with its generation) and feeds the ``(host)`` row:
``gc_s``, ``gc_n``, ``gc_max_s`` (zeros for a run with no collection).  The
hook is removed when the run returns.  No span is named ``executor ...``:
that prefix is left to callers that annotate whole executor calls
themselves.
"""
from __future__ import annotations

import gc
import sys
import time
from contextlib import contextmanager, nullcontext
from contextvars import ContextVar

# the Observability runtime of the ServingEngine.run in progress
_ACTIVE: ContextVar = ContextVar("repro_serving_observability", default=None)

HOST = "(host)"  # registry row of spans not tied to a module


def _profiler():
    """``jax.profiler`` if this process imported JAX, else None."""
    return sys.modules.get("jax.profiler")


def _label(kind: str, module: "str | None", batch: "int | None") -> str:
    name = kind
    if module is not None:
        name = f"{name} {module}"
    if batch is not None:
        name = f"{name} b{batch}"
    return name


def annotate(kind: str, module: "str | None" = None,
             batch: "int | None" = None, *, step: "int | None" = None):
    """A profiler annotation named as a :class:`span` is, feeding no counter:
    a ``StepTraceAnnotation`` numbered ``step`` when given one.  A no-op
    context where the process has no profiler."""
    prof = _profiler()
    if prof is None:
        return nullcontext()
    name = _label(kind, module, batch)
    if step is None:
        return prof.TraceAnnotation(name)
    return prof.StepTraceAnnotation(name, step_num=step)


class span:
    """Context manager for one span of the served path (see module doc).

    ``module`` and ``batch`` complete the annotation's name when given;
    ``module`` (default ``(host)``) is the registry row the seconds land on.
    """

    __slots__ = ("kind", "module", "_ann", "_reg", "_t0")

    def __init__(self, kind: str, module: "str | None" = None,
                 batch: "int | None" = None):
        self.kind = kind
        self.module = module or HOST
        self._ann = annotate(kind, module, batch)
        obs = _ACTIVE.get()
        self._reg = obs.metrics if obs is not None else None

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._reg is not None:
            self._reg.span(self.module, self.kind, time.perf_counter() - self._t0)
        self._ann.__exit__(*exc)


class _GcSpans:
    """``gc.callbacks`` hook: one ``gc`` span per collection."""

    __slots__ = ("reg", "prof", "ann", "t0")

    def __init__(self, reg, prof):
        self.reg, self.prof, self.ann, self.t0 = reg, prof, None, 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            if self.prof is not None:
                self.ann = self.prof.TraceAnnotation(
                    "gc", generation=info["generation"]
                )
                self.ann.__enter__()
            self.t0 = time.perf_counter()
            return
        dt = time.perf_counter() - self.t0
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
            self.ann = None
        if self.reg is not None:
            self.reg.span(HOST, "gc", dt)


@contextmanager
def active(obs):
    """Make ``obs`` (an ``Observability``, or None for a no-op) the runtime
    that spans feed, and span garbage collections, until the block exits."""
    if obs is None:
        yield
        return
    token = _ACTIVE.set(obs)
    if obs.metrics is not None:
        # the (host) row reads gc_s 0 for a run with no collection
        obs.metrics.span(HOST, "gc", 0.0, n=0)
    hook = _GcSpans(obs.metrics, _profiler())
    gc.callbacks.append(hook)
    try:
        yield
    finally:
        gc.callbacks.remove(hook)
        _ACTIVE.reset(token)
