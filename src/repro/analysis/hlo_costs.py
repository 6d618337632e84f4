"""Loop-aware cost extraction from optimized HLO text.

XLA's HloCostAnalysis counts while-loop bodies once, so scanned programs
(layer scans, KV-block scans, grad-accumulation scans) under-report FLOPs and
collective bytes. This module parses the optimized HLO, reconstructs the
computation call graph (while bodies, fusion calls, conditionals), extracts
each while loop's trip count from its condition, and sums

  * dot FLOPs  (2 * prod(result dims) * prod(contracting dims)),
  * convolution FLOPs (2 * prod(result dims) * kernel_elems * Cin/groups),
  * collective result bytes (all-gather / all-reduce / reduce-scatter /
    all-to-all / collective-permute)

scaled by the product of enclosing trip counts. Validated against
cost_analysis() of unrolled programs in tests/test_hlo_costs.py.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}
_COLL_RE = re.compile(
    r"=\s*(?P<result>\(?[^=]*?\)?)\s*"
    r"(?P<op>all-reduce-start|all-gather-start|reduce-scatter|all-to-all|"
    r"collective-permute-start|all-reduce|all-gather|collective-permute)\(")


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` as a plain dict (the installed jax
    returns one flat dict on the CPU and TPU backends alike)."""
    return dict(compiled.cost_analysis())


def compiled_cycles(compiled, *, flops_per_cycle: float = 2.0 * 128 * 128,
                    bytes_per_cycle: float = 128.0) -> float:
    """Roofline cycle estimate from a compiled program's cost analysis.

    Deterministic (static analysis, no wall clock): cycles are the max of
    the compute leg (flops / MXU flops-per-cycle) and the memory leg
    (bytes accessed / HBM bytes-per-cycle), floored at 1. Returns 0.0 when
    the backend reports no usable counters (caller falls back to a modeled
    estimate — kernels/kernel_costs.py).
    """
    d = cost_analysis_dict(compiled)
    flops = float(d.get("flops", 0.0))
    nbytes = float(d.get("bytes accessed", 0.0))
    if flops <= 0.0 and nbytes <= 0.0:
        return 0.0
    return max(1.0, flops / flops_per_cycle, nbytes / bytes_per_cycle)


def _dims(s: str) -> List[int]:
    return [int(x) for x in s.split(",") if x] if s else []


def _shape_of(type_str: str) -> List[Tuple[str, List[int]]]:
    return [(dt, _dims(dd)) for dt, dd in _SHAPE_RE.findall(type_str)]


def _nbytes(type_str: str) -> int:
    total = 0
    for dt, dims in _shape_of(type_str):
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


@dataclass
class Instruction:
    name: str
    result_type: str
    opcode: str
    operands: List[str]
    raw: str


@dataclass
class Computation:
    name: str
    instructions: List[Instruction] = field(default_factory=list)
    types: Dict[str, str] = field(default_factory=dict)   # %name -> type str


# type is everything (incl. tuple types with /*index=N*/ comments) up to the
# first `opcode(` token; lazy match keeps the opcode out of the type group.
_INSTR_RE = re.compile(
    r"^\s*(ROOT\s+)?(?P<name>%[\w\.\-]+)\s*=\s*(?P<type>.*?)"
    r"(?P<opcode>[\w\-]+)\((?P<args>.*)$")


def parse_hlo(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for line in text.splitlines():
        header = re.match(r"^\s*(ENTRY\s+)?(%[\w\.\-]+)\s*\(.*\)\s*->.*\{", line)
        if header:
            cur = Computation(name=header.group(2))
            comps[cur.name] = cur
            if header.group(1):
                comps["__entry__"] = cur
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, tstr, opcode = m.group("name"), m.group("type").strip(), \
            m.group("opcode")
        args = m.group("args")
        operands = re.findall(r"%[\w\.\-]+", args.split("),")[0]) \
            if args else []
        instr = Instruction(name=name, result_type=tstr, opcode=opcode,
                            operands=operands, raw=line)
        cur.instructions.append(instr)
        cur.types[name] = tstr
    return comps


def _attr(raw: str, key: str) -> Optional[str]:
    m = re.search(key + r"=([%\w\.\-]+)", raw)
    return m.group(1) if m else None


def _attr_dims(raw: str, key: str) -> List[int]:
    m = re.search(key + r"=\{([0-9,]*)\}", raw)
    return _dims(m.group(1)) if m else []


def trip_count(comps: Dict[str, Computation], cond_name: str) -> int:
    """Extract the loop bound from a while condition.

    JAX lowers scan/fori to canonical `while i < N` loops; after optimization
    the compare may be wrapped in a fusion whose constant bound operand lives
    in the condition computation. The bound is the max integer constant
    reachable from the condition (0/1 step constants are dominated by N)."""
    cond = comps.get(cond_name)
    if cond is None:
        return 1
    best = 0
    for ins in cond.instructions:
        if ins.opcode == "constant":
            m = re.search(r"constant\((-?\d+)\)", ins.raw)
            if m:
                best = max(best, int(m.group(1)))
        elif ins.opcode in ("fusion", "call"):
            callee = _attr(ins.raw, "calls")
            if callee and callee in comps:
                for ins2 in comps[callee].instructions:
                    if ins2.opcode == "constant":
                        m = re.search(r"constant\((-?\d+)\)", ins2.raw)
                        if m:
                            best = max(best, int(m.group(1)))
    return max(1, best)


def _dot_flops(comp: Computation, ins: Instruction) -> float:
    out_elems = 1
    for _, dims in _shape_of(ins.result_type):
        for d in dims:
            out_elems *= d
    lhs = ins.operands[0] if ins.operands else None
    lhs_type = comp.types.get(lhs, "")
    lhs_shape = _shape_of(lhs_type)
    contract = _attr_dims(ins.raw, "lhs_contracting_dims")
    k = 1
    if lhs_shape:
        dims = lhs_shape[0][1]
        for c in contract:
            if c < len(dims):
                k *= dims[c]
    return 2.0 * out_elems * max(k, 1)


def _conv_flops(comp: Computation, ins: Instruction) -> float:
    out_elems = 1
    for _, dims in _shape_of(ins.result_type):
        for d in dims:
            out_elems *= d
    rhs = ins.operands[1] if len(ins.operands) > 1 else None
    rhs_shape = _shape_of(comp.types.get(rhs, ""))
    if not rhs_shape:
        return 0.0
    kelems = 1
    for d in rhs_shape[0][1]:
        kelems *= d
    # kernel = (spatial..., Cin/g, Cout): flops = 2*out*kelems/Cout
    cout = rhs_shape[0][1][-1] if rhs_shape[0][1] else 1
    return 2.0 * out_elems * kelems / max(cout, 1)


@dataclass
class LoopAwareCosts:
    dot_flops: float = 0.0
    conv_flops: float = 0.0
    collective_bytes: float = 0.0
    coll_by_op: Dict[str, float] = field(default_factory=dict)
    n_while: int = 0
    trip_counts: List[int] = field(default_factory=list)

    @property
    def flops(self) -> float:
        return self.dot_flops + self.conv_flops


def analyze(text: str) -> LoopAwareCosts:
    comps = parse_hlo(text)
    out = LoopAwareCosts()
    entry = comps.get("__entry__")
    if entry is None:
        return out
    seen_whiles: List[int] = []

    def walk(comp: Computation, mult: float, depth: int = 0):
        if depth > 12:
            return
        for ins in comp.instructions:
            if ins.opcode == "dot":
                out.dot_flops += mult * _dot_flops(comp, ins)
            elif ins.opcode == "convolution":
                out.conv_flops += mult * _conv_flops(comp, ins)
            elif ins.opcode == "while":
                body = _attr(ins.raw, "body")
                cond = _attr(ins.raw, "condition")
                t = trip_count(comps, cond) if cond else 1
                out.n_while += 1
                out.trip_counts.append(t)
                if body and body in comps:
                    walk(comps[body], mult * t, depth + 1)
            elif ins.opcode in ("fusion", "call", "custom-call"):
                callee = _attr(ins.raw, "calls")
                if callee and callee in comps:
                    walk(comps[callee], mult, depth + 1)
            elif ins.opcode == "conditional":
                for key in ("true_computation", "false_computation"):
                    callee = _attr(ins.raw, key)
                    if callee and callee in comps:
                        walk(comps[callee], mult, depth + 1)
            m = _COLL_RE.search(ins.raw)
            if m:
                op = m.group("op").replace("-start", "")
                b = mult * _nbytes(m.group("result"))
                out.collective_bytes += b
                out.coll_by_op[op] = out.coll_by_op.get(op, 0.0) + b
    walk(entry, 1.0)
    return out
