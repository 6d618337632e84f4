"""Roofline extraction from compiled dry-run artifacts.

Conventions (important — everything is PER DEVICE):
  * ``compiled.cost_analysis()`` on an SPMD program reports per-partition
    flops / bytes, so terms divide by per-chip peaks only:
        compute_s    = flops / PEAK_FLOPS
        memory_s     = bytes_accessed / HBM_BW
        collective_s = collective_bytes / ICI_BW
  * collective_bytes sums the *result* shapes of every all-gather /
    all-reduce / reduce-scatter / all-to-all / collective-permute in the
    optimized HLO (the per-device receive payload; '-start' ops counted,
    '-done' skipped). This is the wire-byte proxy used throughout
    EXPERIMENTS.md — ring all-reduce moves ~2x this, noted there.
"""
from __future__ import annotations

import json
import re
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional, Tuple

from repro.core.perf_model import HBM_BW, ICI_BW, PEAK_FLOPS

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


@dataclass
class CollectiveOp:
    opcode: str
    bytes: int
    group_size: int = 0


def parse_collectives(hlo_text: str) -> List[CollectiveOp]:
    """Sum per-device result bytes of every collective in optimized HLO."""
    out: List[CollectiveOp] = []
    for line in hlo_text.splitlines():
        if "=" not in line:
            continue
        m = re.search(
            r"=\s*(\(?[a-z0-9_,\[\]\{\}\s]*\)?)\s*"
            r"(all-reduce-start|all-gather-start|reduce-scatter|"
            r"all-to-all|collective-permute-start|all-reduce|all-gather|"
            r"collective-permute)\(", line)
        if not m:
            continue
        opcode = m.group(2).replace("-start", "")
        result = m.group(1)
        nbytes = sum(_shape_bytes(dt, dims)
                     for dt, dims in _SHAPE_RE.findall(result))
        g = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
        group = int(g.group(2)) if g else 0
        out.append(CollectiveOp(opcode=opcode, bytes=nbytes, group_size=group))
    return out


def collective_bytes(hlo_text: str) -> int:
    return sum(op.bytes for op in parse_collectives(hlo_text))


def collective_breakdown(hlo_text: str) -> Dict[str, Tuple[int, int]]:
    """{opcode: (count, total_bytes)}"""
    out: Dict[str, Tuple[int, int]] = {}
    for op in parse_collectives(hlo_text):
        c, b = out.get(op.opcode, (0, 0))
        out[op.opcode] = (c + 1, b + op.bytes)
    return out


def analytic_traffic(cfg, shape, *, params_bytes: float, opt_bytes: float = 0,
                     cache_bytes: float = 0, accum: int = 1,
                     remat: bool = True) -> Dict[str, float]:
    """Modeled per-step global HBM traffic (bytes), by component.

    Assumptions: flash-style attention keeps
    per-block score temporaries in VMEM; weights are re-read from HBM per
    microbatch (fwd + remat-fwd + bwd); a decode step reads the whole cache
    once and writes one row per sequence per layer in place (negligible).
    """
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    d, L, V = cfg.d_model, cfg.num_layers, cfg.vocab_size
    t: Dict[str, float] = {}
    if shape.kind == "train":
        reads_per_ub = 2 + (1 if remat else 0)           # fwd + bwd (+remat)
        t["weights"] = reads_per_ub * accum * params_bytes
        t["optimizer"] = 2 * params_bytes + 2 * opt_bytes     # p r/w + m,v r/w
        t["grads"] = 2 * accum * params_bytes                 # accum buffer r/w
        t["stash"] = 4.0 * tokens * d * L * 2                 # h save w+r (bf16)
        t["logits"] = 4.0 * tokens * V * 2                    # write + read, bf16
        if cfg.moe is not None:
            cap = cfg.moe.capacity_factor * cfg.moe.top_k
            t["moe_dispatch"] = 8.0 * cap * tokens * d * L    # in/out buf w+r
    elif shape.kind == "prefill":
        t["weights"] = params_bytes                      # bf16 serving weights
        t["cache_write"] = cache_bytes
        t["activations"] = 4.0 * tokens * d * L * 2
        t["logits"] = 2.0 * shape.global_batch * V * 2
    else:                                                # decode
        t["weights"] = params_bytes
        t["cache"] = cache_bytes                         # one full read
        t["logits"] = 2.0 * shape.global_batch * V * 2
        t["activations"] = 8.0 * shape.global_batch * d * L * 2
    t["total"] = sum(t.values())
    return t


@dataclass
class CellReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    bound_s: float
    model_flops: float
    useful_ratio: float          # MODEL_FLOPS / (flops_per_device * chips)
    arg_bytes: int = 0
    temp_bytes: int = 0
    out_bytes: int = 0
    hbm_total_gib: float = 0.0
    fits_hbm: bool = True
    coll_by_op: Dict[str, Tuple[int, int]] = field(default_factory=dict)
    note: str = ""
    roofline_frac: float = 0.0   # model-flops time / bound (the §Perf score)
    traffic: Dict[str, float] = field(default_factory=dict)
    xla_bytes_accessed: float = 0.0

    def to_json(self) -> dict:
        return asdict(self)


def build_report(*, arch: str, shape: str, mesh_name: str, chips: int,
                 cost: Dict, mem, hlo_text: str, model_flops: float,
                 traffic: Optional[Dict[str, float]] = None,
                 note: str = "") -> CellReport:
    """Assemble a cell's roofline from the compiled artifact.

    * compute term: loop-aware MXU (dot/conv) FLOPs parsed from optimized HLO
      (hlo_costs.analyze — cost_analysis() undercounts while bodies),
      per-device = parsed (the HLO is already the per-partition program).
    * memory term: analytic HBM traffic model (global / chips); XLA 'bytes
      accessed' is recorded for reference but mixes VMEM-resident temporaries.
    * collective term: loop-aware per-device collective result bytes.
    """
    from repro.analysis.hlo_costs import analyze
    la = analyze(hlo_text)
    flops = float(la.flops)                       # per-device (SPMD program)
    xla_bytes = float(cost.get("bytes accessed", 0.0))
    traffic = traffic or {}
    mem_bytes_dev = traffic.get("total", xla_bytes * chips) / chips
    cbytes = float(la.collective_bytes)
    compute_s = flops / PEAK_FLOPS
    memory_s = mem_bytes_dev / HBM_BW
    collective_s = cbytes / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    arg = int(getattr(mem, "argument_size_in_bytes", 0) or 0)
    tmp = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
    outb = int(getattr(mem, "output_size_in_bytes", 0) or 0)
    alias = int(getattr(mem, "alias_size_in_bytes", 0) or 0)
    hbm = (arg + tmp + outb - alias) / 2 ** 30
    model_time = (model_flops / chips) / PEAK_FLOPS
    rep = CellReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        flops_per_device=flops, bytes_per_device=mem_bytes_dev,
        coll_bytes_per_device=cbytes,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dominant, bound_s=bound,
        model_flops=model_flops,
        useful_ratio=model_flops / max(flops * chips, 1e-9),
        arg_bytes=arg, temp_bytes=tmp, out_bytes=outb,
        hbm_total_gib=hbm, fits_hbm=hbm <= 16.0,
        coll_by_op={k: (0, int(v)) for k, v in la.coll_by_op.items()},
        note=note,
        roofline_frac=model_time / max(bound, 1e-12),
    )
    rep.traffic = {k: float(v) for k, v in traffic.items()}
    rep.xla_bytes_accessed = xla_bytes
    return rep
