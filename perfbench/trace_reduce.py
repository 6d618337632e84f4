"""Reduces a ``jax.profiler`` trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

* device planes are ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds
  one event per operation run and ``XLA Modules`` one per program run;
* busy time is the union of the operation intervals inside the window,
  averaged over the device planes; the idle share is 1 - busy / window;
* the window is the host annotation the benchmark put around it;
* each idle gap on the first device is charged to the innermost host event
  (a benchmark annotation, a span of the program mapped onto the trace's
  clock, or a runtime event of the main thread) that covers its midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
TOP = 10
CHARGED_GAPS = 256       # the longest idle gaps, charged one by one
SHORT_GAPS = "(shorter gaps)"

Interval = Tuple[float, float]            # (start_ns, end_ns)


def module_base(name: str) -> str:
    """'jit__lambda(1670988...)' -> 'jit__lambda'."""
    return name.split("(", 1)[0]


def op_label(name: str) -> str:
    """'%fusion.150 = (f32[4,8]...) fusion(...), kind=kLoop' -> 'fusion.150
    fusion': the instruction and its opcode, without shapes."""
    head, _, rest = name.partition(" = ")
    head = head.lstrip("%")
    m = re.search(r"\}?\s*([a-z][a-z0-9\-_]*)\(", rest)
    return f"{head} {m.group(1)}" if m else head


def union_length(intervals: Sequence[Interval], lo: float, hi: float
                 ) -> Tuple[float, List[Interval]]:
    """Length of the union of ``intervals`` clipped to [lo, hi], and the
    gaps between them inside [lo, hi]."""
    ivs = sorted((max(a, lo), min(b, hi)) for a, b in intervals
                 if b > lo and a < hi)
    total, gaps = 0.0, []
    cur_a = cur_b = None
    edge = lo
    for a, b in ivs:
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
                edge = cur_b
            if a > edge:
                gaps.append((edge, a))
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
        edge = cur_b
    if hi > edge:
        gaps.append((edge, hi))
    return total, gaps


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                           # mean over device planes
    window_ns: Interval
    modules: List[Tuple[str, float, float]]  # (base name, start, end), dev 0
    op_seconds: Dict[str, float]            # op label -> seconds, dev 0
    gap_seconds: Dict[str, float]           # host activity -> idle seconds
    annotations: List[Tuple[str, float, float]]   # benchmark's host events

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, base: str) -> Tuple[int, float]:
        """(runs, device seconds) of the program named ``base``."""
        d = [e - s for n, s, e in self.modules if n == base]
        return len(d), sum(d) * 1e-9

    def breakdown(self) -> dict:
        def top(d):
            return [[k, v] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
        return {"device_ops": top(self.op_seconds),
                "idle_gaps": top(self.gap_seconds)}


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def reduce(profile, window_name: str,
           host_spans: Sequence[Tuple[str, float, float]] = (),
           window_start_s: Optional[float] = None) -> TraceSummary:
    """``profile`` is a ``jax.profiler.ProfileData``. ``host_spans`` are
    the program's own spans, (name, start, end) in ``time.perf_counter``
    seconds, and ``window_start_s`` is that clock's reading as the window's
    annotation opened: the one anchor that moves them onto the trace's
    clock."""
    devices, host_lines = [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            devices.append((plane.name, lines))
        elif plane.name == HOST_PLANE:
            host_lines = [(ln.name, _events(ln)) for ln in plane.lines]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    devices.sort()

    host = [e for _, evs in host_lines for e in evs]
    marks = [e for e in host if e[0] == window_name]
    if not marks:
        raise ValueError(f"no host annotation {window_name!r} in the trace")
    lo, hi = min(m[1] for m in marks), max(m[2] for m in marks)
    annotations = [e for e in host if e[0].startswith("perfbench.")]

    busy, first_gaps = [], None
    for _, lines in devices:
        ops = _events(lines[OPS_LINE]) if OPS_LINE in lines else []
        total, gaps = union_length([(s, e) for _, s, e in ops], lo, hi)
        busy.append(total)
        if first_gaps is None:
            first_gaps, first_ops = gaps, ops
            first_mods = _events(lines[MODULES_LINE]) \
                if MODULES_LINE in lines else []

    by_name: Dict[str, float] = {}
    for name, s, e in first_ops:
        if s >= lo and e <= hi:
            by_name[name] = by_name.get(name, 0.0) + (e - s) * 1e-9
    op_s: Dict[str, float] = {}
    for name, sec in by_name.items():
        k = op_label(name)
        op_s[k] = op_s.get(k, 0.0) + sec
    modules = [(module_base(n), s, e) for n, s, e in first_mods
               if s >= lo and e <= hi]

    # the longest gaps go to the innermost host event covering their
    # midpoint: the main thread's runtime events, the benchmark's
    # annotations, the program's spans. A window holds a gap between
    # nearly every two operations, so the many short ones are summed
    # apart: charging each would cost gaps x events.
    main = [e for name, evs in host_lines
            if name.startswith("main") or name == "python3"
            for e in evs]
    spans = to_trace_clock(host_spans, window_start_s, lo) \
        if host_spans else []
    cover = sorted(main + annotations + spans, key=lambda e: e[2] - e[1])
    c_start = np.array([s for _, s, _ in cover], np.float64)
    c_end = np.array([e for _, _, e in cover], np.float64)
    first_gaps.sort(key=lambda g: g[0] - g[1])
    gap_s: Dict[str, float] = {}
    for a, b in first_gaps[:CHARGED_GAPS]:
        mid = (a + b) / 2
        inside = np.flatnonzero((c_start <= mid) & (mid <= c_end))
        who = cover[inside[0]][0] if len(inside) else "(no host event)"
        gap_s[who] = gap_s.get(who, 0.0) + (b - a) * 1e-9
    rest = sum(b - a for a, b in first_gaps[CHARGED_GAPS:])
    if rest:
        gap_s[SHORT_GAPS] = rest * 1e-9

    return TraceSummary(window_s=(hi - lo) * 1e-9,
                        busy_s=sum(busy) / len(busy) * 1e-9,
                        window_ns=(lo, hi), modules=modules,
                        op_seconds=op_s, gap_seconds=gap_s,
                        annotations=annotations)


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise FileNotFoundError(f"{len(files)} .xplane.pb files under "
                                f"{log_dir}")
    return files[0]


def reduce_dir(log_dir: str, window_name: str, **kw) -> TraceSummary:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(find_xplane(log_dir)), window_name,
                  **kw)


def to_trace_clock(spans: Sequence[Tuple[str, float, float]],
                   anchor_host_s: float, anchor_trace_ns: float
                   ) -> List[Tuple[str, float, float]]:
    """Host spans on ``time.perf_counter`` seconds, moved onto the trace's
    clock by one anchor seen on both (the window's start)."""
    off = anchor_trace_ns - anchor_host_s * 1e9
    return [(n, s * 1e9 + off, e * 1e9 + off) for n, s, e in spans]
