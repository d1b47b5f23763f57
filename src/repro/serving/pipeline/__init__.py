"""Pipelined serving core: per-frame DAG co-simulation with backpressure.

The third simulation layer (after ``arrivals`` and ``frontend``): instead of
replaying modules one at a time with analytic hand-off, every frame
traverses the app DAG as a tracked entity inside one global discrete-event
loop — per-module ingress fed by upstream batch completions,
bounded queues exerting backpressure on upstream dispatch, seeded per-frame
fanout correlated across sibling modules, and closed-loop clients plus
admission control reacting to true instantaneous backlog.

Entry points:

* ``ServingEngine.run(...)`` — the engine builds the stages from a plan
  and returns a ``ServeResult`` whose ``.pipeline`` field holds
  the full :class:`PipelineResult` (per-frame e2e latencies, per-module
  budget-overrun attribution).
* :func:`run_pipeline` — the raw co-simulation over hand-built
  :class:`ModuleStage` objects, for tests and custom topologies.
"""
from .core import PipelineConfig, run_pipeline
from .fanout import AccumulatorFanout, DrawnFanout, FanoutSpec, draw_counts, make_stage_fanouts
from .result import FrameTable, PipelineResult
from .stages import (
    Instance,
    ModuleStage,
    RRDispatcher,
    StageStats,
    StageUpdate,
    TCDispatcher,
    make_dispatcher,
)

__all__ = [
    "AccumulatorFanout",
    "DrawnFanout",
    "FanoutSpec",
    "FrameTable",
    "Instance",
    "ModuleStage",
    "PipelineConfig",
    "PipelineResult",
    "RRDispatcher",
    "StageStats",
    "StageUpdate",
    "TCDispatcher",
    "draw_counts",
    "make_dispatcher",
    "make_stage_fanouts",
    "run_pipeline",
]
