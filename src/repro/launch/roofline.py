"""Roofline analysis from the compiled dry-run artifact.

Three terms per (arch x shape x mesh), all in seconds per step:

  compute    = HLO_FLOPs / (chips x peak_FLOP/s)
  memory     = HLO_bytes / (chips x HBM_bw)
  collective = collective_bytes / (chips x link_bw)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()`` (per-device
program, multiplied back to the full mesh); collective_bytes is parsed from
the partitioned HLO text: operand sizes of all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute ops, with a ring-algorithm
wire factor (2x for all-reduce, 1x otherwise).

Hardware constants: the TPU v5e entry of `repro.profiling.hardware`.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

from ..profiling.hardware import TPU_V5E

PEAK_FLOPS = TPU_V5E.peak_flops_bf16
HBM_BW = TPU_V5E.hbm_bw
LINK_BW = TPU_V5E.ici_bw

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# e.g.  %all-gather.2 = f32[16,1,192]{1,0,2} all-gather(%copy.27), ...
#       %ar = (f32[8], f32[8]) all-reduce-start(...)
_COLL_RE = re.compile(
    r"=\s*([\w\(\)\[\],{} ]*?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(",
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Total bytes of (possibly tuple) HLO result type string."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    bytes_by_op: dict[str, int] = field(default_factory=dict)
    count_by_op: dict[str, int] = field(default_factory=dict)

    @property
    def wire_bytes(self) -> float:
        """Per-device bytes on the wire (ring model: 2x for all-reduce)."""
        total = 0.0
        for op, b in self.bytes_by_op.items():
            factor = 2.0 if op == "all-reduce" else 1.0
            total += factor * b
        return total

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_op.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum collective bytes, weighting ops inside while bodies (lax.scan over
    layer segments) by the loop trip count."""
    comps = _split_computations(hlo_text)
    trip: dict[str, int] = {}  # body computation -> trip count
    calls: dict[str, list[str]] = {name: [] for name in comps}
    for name, lines in comps.items():
        for line in lines:
            mw = _WHILE_RE.search(line)
            if mw:
                cond, body = mw.group("cond"), mw.group("body")
                calls[name].append(body)
                trip[body] = _trip_count(comps.get(cond, []))
            for mc in _CALL_RE.finditer(line):
                callee = mc.group(1)
                if callee in comps:
                    calls[name].append(callee)

    # propagate multipliers from the entry computation
    mult: dict[str, int] = {}
    entry = next((n for n, l in comps.items() if l and l[0].startswith("ENTRY")), None)

    def visit(name: str, m: int):
        if mult.get(name, 0) >= m:
            return
        mult[name] = m
        for callee in calls.get(name, []):
            visit(callee, m * trip.get(callee, 1))

    if entry:
        visit(entry, 1)

    stats = CollectiveStats()
    for name, lines in comps.items():
        m_factor = mult.get(name, 1)
        for line in lines:
            m = _COLL_RE.search(line)
            if not m:
                continue
            if m.group(3) == "-done":
                continue  # avoid double counting start/done pairs
            op = m.group(2).lower()
            b = _shape_bytes(m.group(1)) * m_factor
            stats.bytes_by_op[op] = stats.bytes_by_op.get(op, 0) + b
            stats.count_by_op[op] = stats.count_by_op.get(op, 0) + m_factor
    return stats


_WHILE_RE = re.compile(r"while\(.*?\), condition=%?(?P<cond>[\w.\-]+), body=%?(?P<body>[\w.\-]+)")
_CALL_RE = re.compile(r"(?:calls=|to_apply=)%?([\w.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _split_computations(hlo_text: str) -> dict[str, list[str]]:
    """Split HLO text into computation blocks keyed by computation name."""
    comps: dict[str, list[str]] = {}
    cur: str | None = None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if not line.startswith(" ") and ("{" in line) and ("(" in line):
            # computation header: '%name (args) -> type {' or 'ENTRY %name ...'
            header = line.split("(")[0].strip()
            name = header.replace("ENTRY", "").strip().lstrip("%").strip()
            cur = name
            comps[cur] = [line.strip()]
            if "ENTRY" in line:
                comps[cur][0] = "ENTRY " + comps[cur][0]
        elif cur is not None:
            comps[cur].append(stripped)
            if stripped == "}":
                cur = None
    return comps


def _trip_count(cond_lines: list[str]) -> int:
    """Best-effort trip count: the largest integer constant in the condition."""
    best = 1
    for line in cond_lines:
        for m in _CONST_RE.finditer(line):
            best = max(best, int(m.group(1)))
    return best


@dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    chips: int

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.collective_bytes_per_device / LINK_BW

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def as_dict(self) -> dict:
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "chips": self.chips,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "step_s": self.step_s,
        }


def roofline_from_compiled(
    compiled, chips: int, *, scan_correction: float = 1.0
) -> tuple[Roofline, CollectiveStats, dict]:
    """``scan_correction`` compensates cost_analysis counting each while-loop
    (lax.scan segment) body once: it is the analytic ratio of true layer work
    to once-per-segment layer work (see launch.dryrun.scan_correction)."""
    cost = compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0)) * scan_correction
    byts = float(cost.get("bytes accessed", 0.0)) * scan_correction
    text = compiled.as_text()
    colls = parse_collectives(text)
    mem = {}
    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", 0),
            "output_bytes": getattr(ma, "output_size_in_bytes", 0),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", 0),
            "code_bytes": getattr(ma, "generated_code_size_in_bytes", 0),
        }
        mem["total_bytes"] = (
            mem["argument_bytes"] + mem["output_bytes"] + mem["temp_bytes"]
        )
    except Exception as e:  # pragma: no cover
        mem = {"error": str(e)}
    rl = Roofline(
        flops_per_device=flops,
        bytes_per_device=byts,
        collective_bytes_per_device=colls.wire_bytes,
        chips=chips,
    )
    return rl, colls, mem
