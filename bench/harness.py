"""One run of one cell: set-up, the timed window, the check, the metrics.

The window drives the served path that ``python -m repro.launch.serve --real
--pipeline`` composes: ``Planner().plan`` on the attached chip's profile, one
``ModuleExecutor`` per module at published widths, and
``ServingEngine(plan, executors).run(..., pipeline=True,
service_time=LiveServiceTime(executors, cache=False))``, so every batch the
loop starts, part-filled ones included, is a real forward on the chip.
Request latency is kept in the loop's clock, in which each batch takes the
time its forward measured.

The window is a sequence of ``engine.run`` chunks of ``chunk_requests``
requests whose arrivals come from the seed and the chunk's index; it closes
at the first chunk boundary after ``seconds`` of wall time.  Rates are over
all requests and all wall time of those chunks, tails over all requests.
"""
from __future__ import annotations

import gc
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import jax
import numpy as np

from bench import manifest, traffic, weights
from bench.peaks import peaks_for


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CompiledInWindow(RuntimeError):
    """Something traced or compiled inside the measured window."""


# ------------------------------------------------------------ compile watch
_COMPILING = ("/jax/core/compile/", "/jax/compilation_cache/cache_hits")


class CompileWatch:
    """Counts JAX trace/compile events while armed (one listener per process)."""

    _installed: "CompileWatch | None" = None

    def __init__(self):
        self.armed = False
        self.events: list[str] = []

    @classmethod
    def get(cls) -> "CompileWatch":
        if cls._installed is None:
            watch = cls()
            jax.monitoring.register_event_duration_secs_listener(watch._on)
            cls._installed = watch
        return cls._installed

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and event.startswith(_COMPILING):
            self.events.append(f"{event} {kw.get('fun_name', '')}".strip())


# ------------------------------------------------------------ the run record
@dataclass
class Run:
    """What one run saw: what the per-layer readers read (``read(run)``)."""

    cell: "manifest.Cell"
    archs: dict            # module -> arch dict of the configuration
    seq: int
    plan: object           # repro Plan
    results: list          # one ServeResult per chunk
    chunk_s: list          # wall seconds of each chunk
    measured: dict         # (module, batch) -> step seconds, in the window
    peaks: dict
    fill: "dict | None" = None       # members / phantoms / slots (traced run)
    trace: "object | None" = None    # bench.trace_reduce.Summary (traced run)
    traced_forwards: dict = field(default_factory=dict)  # (module, batch) -> n

    @property
    def window_s(self) -> float:
        return float(sum(self.chunk_s))


class _KeepLast:
    """The executor as the loop sees it: runs it, keeps its last output per
    batch size on the device (no host copy in the window)."""

    def __init__(self, module, ex, kept, annotate):
        self.module, self.ex, self.kept, self.annotate = module, ex, kept, annotate

    def __call__(self, b: int):
        if self.annotate:
            with jax.profiler.TraceAnnotation(f"executor {self.module} b{b}"):
                out = self.ex(b)
        else:
            out = self.ex(b)
        self.kept[self.module, b] = out
        return out


def _arch_config(arch: dict):
    from repro.configs.base import ArchConfig

    return ArchConfig(**{k: tuple(v) if isinstance(v, list) else v for k, v in arch.items()})


def _fill_counter():
    """An observability sink whose registry also keeps run totals of batch
    members, phantom members and slots (``batch_fill``)."""
    from repro.serving.observability import Observability, ObservabilityConfig
    from repro.serving.observability.metrics import MetricsRegistry

    class FillCounter(MetricsRegistry):
        __slots__ = ("totals",)

        def __init__(self):
            super().__init__()
            self.totals = {"members": 0, "phantoms": 0, "slots": 0}

        def batch(self, module, size, cap, n_phantom, dur):
            t = self.totals
            t["members"] += size
            t["phantoms"] += n_phantom
            t["slots"] += cap
            super().batch(module, size, cap, n_phantom, dur)

        def bulk(self, module, *, batches, members, phantoms, slots, busy):
            t = self.totals
            t["members"] += members
            t["phantoms"] += phantoms
            t["slots"] += slots
            super().bulk(module, batches=batches, members=members,
                         phantoms=phantoms, slots=slots, busy=busy)

    obs = Observability(ObservabilityConfig(trace=False, metrics=True))
    obs.metrics = FillCounter()
    return obs


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------- set-up
def build(cell, seed: int, hw_name: str):
    """Plan, executors with the benchmark's seeded weights and tokens, warmed
    at every batch size the plan uses.  Returns (plan, executors, archs,
    params, tokens, seq)."""
    from repro.core import Leaf, Workload, series
    from repro.core.dag import AppDAG
    from repro.core.harpagon import Planner
    from repro.launch.serve import ModuleExecutor, plan_batches
    from repro.profiling import arch_profile

    mix = cell.traffic
    seq = int(mix["prompt_tokens"])
    mods = cell.config["modules"]
    archs = {m["name"]: m["arch"] for m in mods}
    cfgs = {m["name"]: _arch_config(m["arch"]) for m in mods}
    profiles = {n: arch_profile(c, seq=seq, hardware=(hw_name,)) for n, c in cfgs.items()}
    dag = AppDAG("session", series(*[Leaf(n) for n in archs]))
    wl = Workload(dag, {n: float(mix["rate"]) for n in archs}, float(mix["slo_s"]))
    plan = Planner().plan(wl, profiles)
    if not plan.feasible:
        raise RuntimeError(f"the planner finds no feasible plan for {cell.name}")
    executors, params, tokens = {}, {}, {}
    for i, (n, cfg) in enumerate(cfgs.items()):
        ex = ModuleExecutor(cfg, seq=seq)
        shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), ex.params)
        # the benchmark's seeded weights replace the executor's fixed-key ones
        for leaf in jax.tree.leaves(ex.params):
            leaf.delete()
        ex.params = params[n] = weights.make_params(shapes, seed, i)
        executors[n] = ex
    for n, bs in plan_batches(plan).items():
        i = list(archs).index(n)
        for b in bs:
            executors[n](b)  # compiles (or loads) the forward at this batch
            tokens[n, b] = weights.make_tokens(seed, i, b, seq, archs[n]["vocab_size"])
            executors[n]._tokens[b] = tokens[n, b]
            executors[n](b)  # one warm forward on the seeded token block
    return plan, executors, archs, params, tokens, seq


# ------------------------------------------------------------------ checks
def check_accounting(results, n, plan, measured_per_chunk) -> dict:
    """Serving-loop and plan checks: each is a count or share, limit given.

    * ``unaccounted``: offered requests not completed, shed, dropped or
      skipped exactly once;
    * ``member_gap``: per module, served instances against its fanout times
      the frames that passed it;
    * ``unexecuted``: batches the loop started that were not a forward on the
      chip (and forwards no batch asked for);
    * ``rate_shortfall``: the largest share of a module's rate its schedule's
      machines do not cover.
    """
    wl = plan.workload
    frame_rate = next(iter(wl.rates.values()))
    unaccounted = member_gap = unexecuted = 0
    for res, fwd in zip(results, measured_per_chunk):
        p = res.pipeline
        kinds = np.stack([p.completed, p.shed, p.dropped, p.skipped]).astype(int)
        unaccounted += int((kinds.sum(0) != 1).sum()) + abs(n - p.e2e.size)
        for m in p.modules:
            passed = int((~np.isnan(p.finish[m])).sum())
            want = wl.rates[m] / frame_rate * passed
            member_gap += abs(len(res.module_stats[m].latencies) - round(want))
            unexecuted += abs(res.module_stats[m].batches - fwd.get(m, 0))
    short = 0.0
    for m, s in plan.schedules.items():
        cap = sum(a.machines * a.config.batch / a.config.duration for a in s.allocs)
        short = max(short, (s.rate - cap) / s.rate)
    return {
        "unaccounted": (float(unaccounted), 0.0),
        "member_gap": (float(member_gap), 0.0),
        "unexecuted": (float(unexecuted), 0.0),
        "rate_shortfall": (float(max(short, 0.0)), 1e-9),
    }


def check_logits(cell, kept, params, tokens, archs) -> dict:
    """Per module, the widest logit gap of every kept output (the last one of
    each batch size the window ran) against the plain reference, with the
    module's limit.  A module the window never ran reads 1e30."""
    ref = manifest.reference(cell.config["reference"], cell.bench_dir)
    out = {}
    for mod in cell.config["modules"]:
        m, worst = mod["name"], None
        for (km, b), logits in sorted(kept.items()):
            if km != m:
                continue
            g, a = ref.widest_gap(params[m], tokens[m, b], logits, archs[m], mod["rms_norm_eps"])
            _log(f"check: {m} b{b}: widest logit gap {g!r}, top-token agreement {a!r}")
            worst = g if worst is None else max(worst, g)
        out[f"logit_gap.{m}"] = (1e30 if worst is None else worst, float(mod["logit_gap_limit"]))
    return out


# ------------------------------------------------------------------ window
def window(engine, live, mix, n, seed, seconds, *, scale=1.0, obs=None, trace_dir=None):
    """Run ``engine.run`` chunks of ``n`` requests until ``seconds`` of wall
    time have passed, closing on a chunk boundary.  With ``trace_dir`` the
    profiler traces the second chunk, and the window runs at least two.
    ``scale`` multiplies the provisioned rate into the offered one.

    Returns (results, chunk wall seconds, forwards per module per chunk,
    forwards per (module, batch) in the traced chunk)."""
    rate = float(mix["rate"])
    results, chunk_s, per_chunk, traced = [], [], [], {}
    start = time.perf_counter()
    chunk = 0
    while True:
        arr = traffic.arrivals(mix, n, seed, chunk, scale)
        before = {k: len(v) for k, v in live.measured.items()}
        profiled = trace_dir is not None and chunk == 1
        if profiled:
            jax.profiler.start_trace(str(trace_dir))
        t = time.perf_counter()
        res = engine.run(
            n, rate, arrivals=arr, seed=(seed + chunk) % 2**31,
            timeout=mix.get("timeout"), offered_rate=rate * scale, pipeline=True,
            service_time=live, observability=obs,
        )
        chunk_s.append(time.perf_counter() - t)
        if profiled:
            jax.profiler.stop_trace()
        results.append(res)
        fwd: dict = {}
        for (m, b), v in live.measured.items():
            d = len(v) - before.get((m, b), 0)
            fwd[m] = fwd.get(m, 0) + d
            if profiled and d:
                traced[m, b] = d
        per_chunk.append(fwd)
        chunk += 1
        if time.perf_counter() - start >= seconds and (trace_dir is None or chunk >= 2):
            return results, chunk_s, per_chunk, traced


# -------------------------------------------------------------------- a run
def run_cell(
    root: Path,
    name: str,
    *,
    seed: int,
    seconds: float,
    trace: bool,
    t0: float,
    require_chip: bool = True,
    device_kind: "str | None" = None,
    trace_dir: "Path | None" = None,
) -> dict:
    """Run cell ``name`` once and return the result line's object.

    ``require_chip=False`` and ``device_kind`` let a CPU test drive the rest
    of a run at a tiny size; the benchmark itself never sets them.
    """
    cell = manifest.load_cell(root, name)
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise NoChip(
            f"cell {name} needs {cell.chips} TPU chip(s); JAX has "
            f"{len(devs)} {devs[0].platform} device(s)"
        )
    kind = device_kind or devs[0].device_kind
    peaks = peaks_for(kind)
    from repro.profiling import spec_for
    from repro.serving import LiveServiceTime, ServingEngine

    plan, executors, archs, params, tokens, seq = build(cell, seed, spec_for(kind).name)
    _log(plan.summary())
    kept: dict = {}
    wrapped = {m: _KeepLast(m, ex, kept, trace) for m, ex in executors.items()}
    live = LiveServiceTime(wrapped, cache=False)
    engine = ServingEngine(plan, executors=wrapped)
    mix = cell.traffic
    n = int(cell.params["chunk_requests"])
    obs = _fill_counter() if trace else None
    watch = CompileWatch.get()
    jax.effects_barrier()
    # What set-up made lives for the whole run: move it out of the collector's
    # reach, so that a full collection inside a timed executor call scans only
    # what the window makes (a full pass over set-up's objects took tens of ms
    # and landed in measured step times, and so in the loop's latencies).
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t0

    watch.events.clear()
    watch.armed = True
    win = window(engine, live, mix, n, seed, seconds, scale=float(mix.get("offered_scale", 1.0)),
                 obs=obs, trace_dir=trace_dir if trace else None)
    watch.armed = False
    if watch.events:
        raise CompiledInWindow(f"{len(watch.events)} compile events in the window: {watch.events[:5]}")
    results, chunk_s, per_chunk, traced = win

    dev = devs[0]
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    _log(f"peak_bytes_in_use {peak}")
    measured = {k: list(v) for k, v in live.measured.items()}
    for (m, b), v in sorted(measured.items()):
        _log(f"steps {m} b{b}: {len(v)} forwards, median {1e3 * float(np.median(v))!r} ms, "
             f"sum {float(np.sum(v))!r} s")
    _log(f"window {sum(chunk_s)!r} s over {len(chunk_s)} chunks")

    run = Run(cell, archs, seq, plan, results, chunk_s, measured, peaks)
    if trace:
        from bench import trace_reduce

        run.fill = dict(obs.metrics.totals)
        run.trace = trace_reduce.reduce_dir(trace_dir, window_s=chunk_s[1])
        run.traced_forwards = traced

    # the program's state goes before the reference runs: compiled forwards
    # and the executors' own tables; the benchmark's weights, tokens and the
    # kept outputs stay
    for ex in executors.values():
        ex.compiled.clear()
    del engine, live, wrapped, executors
    gc.collect()

    checks = {
        **check_logits(cell, kept, params, tokens, archs),
        **check_accounting(results, n, plan, per_chunk),
    }
    correct = all(v <= lim for v, lim in checks.values())

    machines = sum(a.machines for s in plan.schedules.values() for a in s.allocs)
    e2e_all = np.concatenate([np.asarray(r.e2e_latencies, float) for r in results])
    offered = sum(r.offered for r in results)
    met = int((e2e_all <= plan.workload.slo + 1e-9).sum())
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devs),
        "memory_peak_bytes": peak,
    }
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = manifest.metric_reader(m["name"], cell.bench_dir)(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
    else:
        values = {
            "served_rps": e2e_all.size / run.window_s,
            "p99_latency_ms": 1e3 * float(np.quantile(e2e_all, 0.99)) if e2e_all.size else float("inf"),
            "slo_attainment": met / offered if offered else 0.0,
            "plan_cost": machines * peaks["unit_price"],
            "setup_s": setup_s,
        }
        metrics = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end
        }
    out = {
        "correct": bool(correct),
        "attempted": int(offered),
        "failed": int(offered - e2e_all.size),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        out["breakdown"] = run.trace.breakdown()
    out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return out
