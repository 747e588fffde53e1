"""Roofline terms from compiled XLA artifacts (no hardware required).

Three terms per (arch × train shape × mesh), in seconds:

    compute    = FLOPs_global     / (chips × peak_FLOPs)
    memory     = HBM_bytes_global / (chips × HBM_bw)
    collective = collective_bytes_per_chip / link_bw

FLOPs and HBM bytes come from the analytic cell model
(``repro.roofline.analytic``), since ``compiled.cost_analysis()`` counts
each scan body once; collective bytes take the larger of that model and
the operand sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute op parsed from the (per-device SPMD) HLO
text.

TPU v5e-like constants: 197 bf16 TFLOP/s, 819 GB/s HBM, ~50 GB/s/link ICI.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(pred|[suf]\d+|bf16|c64|c128)\[([\d,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.*?)\s+"
    r"([\w\-]+)(?:\.\d+)?\(([^)]*)\)"
)


def _shape_bytes(text: str) -> int:
    """Total bytes of all array shapes appearing in ``text``."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES.get(dtype, 4)
    return total


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: dict
    count_by_kind: dict

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())

    @property
    def total_count(self) -> int:
        return sum(self.count_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum operand sizes of every collective op in an HLO module dump."""
    # First pass: instruction name -> output bytes.
    out_bytes: dict[str, int] = {}
    lines = hlo_text.splitlines()
    for ln in lines:
        mm = _INSTR_RE.match(ln)
        if mm:
            name, shape_txt, _, _ = mm.groups()
            out_bytes[name] = _shape_bytes(shape_txt)

    bytes_by_kind = {k: 0 for k in _COLLECTIVES}
    count_by_kind = {k: 0 for k in _COLLECTIVES}
    for ln in lines:
        mm = _INSTR_RE.match(ln)
        if not mm:
            continue
        name, shape_txt, op, args = mm.groups()
        kind = None
        for c in _COLLECTIVES:
            if op == c or op.startswith(c + "-"):
                kind = c
                break
        if kind is None:
            continue
        # Operand sizes: look up named operands; fall back to output size.
        operands = 0
        for a in args.split(","):
            a = a.strip().lstrip("%")
            a = a.split(" ")[-1].lstrip("%")
            if a in out_bytes:
                operands += out_bytes[a]
        if operands == 0:
            operands = _shape_bytes(shape_txt)
        bytes_by_kind[kind] += operands
        count_by_kind[kind] += 1
    return CollectiveStats(bytes_by_kind, count_by_kind)


def _split_computations(hlo_text: str) -> dict[str, list[str]]:
    """Computation name -> its instruction lines."""
    comps: dict[str, list[str]] = {}
    current = None
    header = re.compile(
        r"^\s*(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$"
    )
    for ln in hlo_text.splitlines():
        m = header.match(ln)
        if m:
            current = m.group(1)
            comps[current] = []
            continue
        if ln.strip() == "}":
            current = None
            continue
        if current is not None:
            comps[current].append(ln)
    return comps


_WHILE_RE = re.compile(
    r"while\(.*?\).*condition=%?([\w\.\-]+).*body=%?([\w\.\-]+)"
)
_CALL_COMP_RE = re.compile(
    r"(?:calls=|to_apply=|body=|condition=|branch_computations=\{)"
    r"%?([\w\.\-]+(?:,\s*%?[\w\.\-]+)*)"
)
_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")


def _trip_count(cond_lines: list[str]) -> int:
    """Largest s32 scalar constant in a while condition ≈ the trip count."""
    best = 1
    for ln in cond_lines:
        for c in _CONST_RE.findall(ln):
            best = max(best, int(c))
    return best


def parse_collectives_nested(hlo_text: str) -> CollectiveStats:
    """Collective bytes with while-body (scan) trip-count multiplication.

    XLA lowers lax.scan to `while`; a naive line scan counts each body
    once. Here every computation's collective bytes are weighted by the
    product of enclosing loop trip counts (trip parsed from the largest
    s32 constant in the loop condition — exact for jax scans).
    """
    comps = _split_computations(hlo_text)
    if not comps:
        return parse_collectives(hlo_text)

    # instruction name -> bytes (across all computations)
    out_bytes: dict[str, int] = {}
    for lines in comps.values():
        for ln in lines:
            mm = _INSTR_RE.match(ln)
            if mm:
                out_bytes[mm.group(1)] = _shape_bytes(mm.group(2))

    # computation -> multiplicity, propagated from callers. Iterate to a
    # fixpoint over the call graph (it is a DAG).
    mult: dict[str, float] = {name: 0.0 for name in comps}
    # Roots: computations never referenced by others.
    referenced = set()
    for lines in comps.values():
        for ln in lines:
            for grp in _CALL_COMP_RE.findall(ln):
                for nm in grp.split(","):
                    referenced.add(nm.strip().lstrip("%"))
    for name in comps:
        if name not in referenced:
            mult[name] = 1.0
    for _ in range(len(comps)):
        changed = False
        for name, lines in comps.items():
            m_self = mult.get(name, 0.0)
            if m_self <= 0:
                continue
            for ln in lines:
                wm = _WHILE_RE.search(ln)
                if wm:
                    cond, body = wm.groups()
                    trips = _trip_count(comps.get(cond, []))
                    for target, factor in ((body, trips), (cond, trips + 1)):
                        new = m_self * factor
                        if target in mult and new > mult[target]:
                            mult[target] = new
                            changed = True
                else:
                    for grp in _CALL_COMP_RE.findall(ln):
                        for nm in grp.split(","):
                            nm = nm.strip().lstrip("%")
                            if nm in mult and m_self > mult[nm]:
                                mult[nm] = m_self
                                changed = True
        if not changed:
            break

    bytes_by_kind = {k: 0.0 for k in _COLLECTIVES}
    count_by_kind = {k: 0 for k in _COLLECTIVES}
    for name, lines in comps.items():
        w = mult.get(name, 0.0) or 1.0
        for ln in lines:
            mm = _INSTR_RE.match(ln)
            if not mm:
                continue
            _, shape_txt, op, args = mm.groups()
            kind = None
            for c in _COLLECTIVES:
                if op == c or op.startswith(c + "-"):
                    kind = c
                    break
            if kind is None:
                continue
            operands = 0
            for a in args.split(","):
                a = a.strip().lstrip("%").split(" ")[-1].lstrip("%")
                if a in out_bytes:
                    operands += out_bytes[a]
            if operands == 0:
                operands = _shape_bytes(shape_txt)
            bytes_by_kind[kind] += w * operands
            count_by_kind[kind] += 1
    return CollectiveStats(
        {k: int(v) for k, v in bytes_by_kind.items()}, count_by_kind
    )


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops_global: float
    hlo_bytes_global: float
    collective_bytes_per_chip: float
    collective_breakdown: dict
    model_flops_global: float
    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_fraction(self) -> float:
        if self.hlo_flops_global <= 0:
            return float("nan")
        return self.model_flops_global / self.hlo_flops_global

    @property
    def roofline_fraction(self) -> float:
        """Fraction of peak compute achieved when running at the modeled
        bound: (model FLOPs / chips / peak) / bound-time."""
        if self.bound_s <= 0:
            return float("nan")
        ideal = self.model_flops_global / (self.chips * PEAK_FLOPS)
        return ideal / self.bound_s

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["dominant"] = self.dominant
        d["bound_s"] = self.bound_s
        d["useful_flops_fraction"] = self.useful_flops_fraction
        d["roofline_fraction"] = self.roofline_fraction
        return d


def model_flops(cfg, shape, num_agents: int = 1) -> float:
    """MODEL_FLOPS: 6·N·D for a training step, per the standard rule;
    N = active params, D = total tokens."""
    from repro.models import model as M

    n_total = M.parameter_count(cfg)
    # Active params for MoE: replace expert FFN params with top_k experts.
    n_active = n_total
    if cfg.num_experts > 0:
        moe_layers = sum(
            1 for k in cfg.block_pattern if k.endswith("_moe")
        ) * cfg.num_groups
        per_expert = 3 * cfg.d_model * cfg.d_ff
        n_active = (
            n_total
            - moe_layers * cfg.num_experts * per_expert
            + moe_layers * cfg.num_experts_per_token * per_expert
        )
    return 6.0 * n_active * shape.global_batch * shape.seq_len


def report(
    *,
    arch: str,
    shape,
    cfg,
    mesh_name: str,
    chips: int,
    cost: dict | None,
    hlo_text: str,
    num_agents: int = 1,
    remat: bool = True,
    tcfg=None,
    mesh_shape: dict | None = None,
    gossip_directed_edges: int = 0,
) -> RooflineReport:
    """Primary numbers come from the analytic cell model (repro.roofline.
    analytic) — XLA cost_analysis counts scan bodies once, so it is kept
    only as recorded metadata. Collective bytes take the max of the
    analytic model and the trip-aware HLO parse."""
    from repro.configs.base import TrainConfig
    from repro.roofline import analytic

    coll = parse_collectives_nested(hlo_text)
    mesh_shape = mesh_shape or {"total": chips}
    cell = analytic.train_model(
        cfg, shape, tcfg or TrainConfig(), mesh_shape, num_agents,
        gossip_directed_edges,
    )

    flops_global = cell.flops_global
    bytes_global = cell.hbm_bytes_global
    coll_per_chip = max(cell.collective_bytes_per_chip, float(coll.total_bytes))
    breakdown = dict(coll.bytes_by_kind)
    breakdown["analytic"] = dict(cell.collective_detail)
    return RooflineReport(
        arch=arch,
        shape=shape.name,
        mesh=mesh_name,
        chips=chips,
        hlo_flops_global=flops_global,
        hlo_bytes_global=bytes_global,
        collective_bytes_per_chip=coll_per_chip,
        collective_breakdown=breakdown,
        model_flops_global=model_flops(cfg, shape, num_agents),
        compute_s=flops_global / (chips * PEAK_FLOPS),
        memory_s=bytes_global / (chips * HBM_BW),
        collective_s=coll_per_chip / ICI_BW,
    )
