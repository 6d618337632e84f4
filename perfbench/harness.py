"""What every cell shares: finding a cell's files by name, the device gate,
the compile cache, the measured window, the profiler, the metric readers
and the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``. Everything that
belongs to one configuration, traffic mix, metric or cell lives in a file
of its own, found by its name:

  configs/<config>.json    sizes, the job that runs it, its source
  traffic/<traffic>.json   the mix's parameters, read by the job
  jobs/<job>.py            ``run(Run) -> Outcome``, one per job
  families/<family>.py     a serve configuration's program side
  reference/<family>.py    its plain reference and seeded weights
  metrics/<metric>.py      ``read(record) -> float | None``, one per metric
  checks/<workload>.json   the limit of each number ``correct`` compares
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoDevice(SystemExit):
    """Raised, with a non-zero exit code, when the cell's chips are absent."""

    def __init__(self, msg: str):
        print(f"perfbench: {msg}", file=sys.stderr)
        super().__init__(3)


# ------------------------------------------------------------------ cells
def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict                  # configs/<config>.json
    traffic: dict                 # traffic/<traffic>.json
    checks: Dict[str, dict]       # checks/<workload>.json: name -> limit
    end_to_end: List[dict]        # BENCHMARK.json metrics this cell reports
    per_layer: List[dict]
    root: str = ROOT              # the checkout that holds the cell's files


def _metrics_for(entries: List[dict], cell: str, e2e_names=None):
    out = []
    for m in entries:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif e2e_names is None or m.get("moves") in e2e_names:
            out.append(m)
    return out


def load_cell(name: str, root: str = ROOT, bench: Optional[dict] = None
              ) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _read_json(os.path.join(root, cfg_entry["file"]))
    traffic = _read_json(os.path.join(root, "perfbench", "traffic",
                                      w["traffic"] + ".json"))
    checks = _read_json(os.path.join(root, "perfbench", "checks",
                                     name + ".json"))
    e2e = _metrics_for(bench["end_to_end"], name)
    per_layer = _metrics_for(bench["per_layer"], name,
                             {m["name"] for m in e2e})
    return Cell(name, int(w["chips"]), config, traffic, checks, e2e,
                per_layer, root)


def load_module(kind: str, name: str, root: str = ROOT):
    """``perfbench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(root, "perfbench", kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no perfbench/{kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------- device
def setup_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else ``.jax_cache`` in the checkout (a fixed path: the path is part
    of the cache key). Every program is cached, however fast it compiled,
    so that a second run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def require_devices(chips: int):
    """The first ``chips`` accelerator devices, or ``NoDevice``."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:          # no backend could start
        raise NoDevice(f"JAX found no device: {e}")
    if devs[0].platform != "tpu":
        raise NoDevice(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    return devs[:chips]


def device_info(devices) -> dict:
    import jax
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts XLA compilations (cache hits included) while installed."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        self.seconds = 0.0

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)
        return False


def span_seconds(tracer) -> Dict[str, float]:
    """Seconds per span name over a ``repro.obs`` tracer's events; empty
    where no tracer was installed."""
    out: Dict[str, float] = {}
    for e in (tracer.events if tracer else []):
        out[e["name"]] = out.get(e["name"], 0.0) + e["t1"] - e["t0"]
    return out


# ---------------------------------------------------------------- runs
@dataclasses.dataclass
class Run:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_process: float              # perf_counter at process start
    devices: list
    control: bool = False         # also read the control (calibrate.py)


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    record: dict                  # what the metric readers read
    checks: List[Check]
    memory_peak_bytes: Optional[int]
    trace: Optional[object] = None    # trace_reduce.TraceSummary
    values: Optional[dict] = None     # each compared number
    control: Optional[dict] = None    # the same numbers of the control


class Profiler:
    """``jax.profiler`` around a window, written under ``TMPDIR`` and
    removed once reduced."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = None

    def __enter__(self):
        if self.enabled:
            import jax
            self.dir = tempfile.mkdtemp(prefix="perfbench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # runtime events only
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        if self.enabled:
            import jax
            t0 = now()
            jax.profiler.stop_trace()
            print(f"trace: written in {now() - t0:.1f} s", file=sys.stderr)
        return False

    def annotate(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def reduce(self, window_name: str, **kw):
        if not self.enabled:
            return None
        from perfbench import trace_reduce
        t0 = now()
        try:
            return trace_reduce.reduce_dir(self.dir, window_name, **kw)
        finally:
            print(f"trace: reduced in {now() - t0:.1f} s", file=sys.stderr)
            shutil.rmtree(self.dir, ignore_errors=True)


def check_limits(cell: Cell, values: Dict[str, float]) -> List[Check]:
    """Pairs each compared number with its limit from the cell's checks
    file; a number the file names but the run did not give is NaN."""
    return [Check(name, float(values.get(name, float("nan"))),
                  float(spec["limit"]))
            for name, spec in cell.checks.items()]


def metric_values(cell: Cell, outcome: Outcome, trace: bool,
                  root: str = ROOT) -> Dict[str, dict]:
    entries = cell.per_layer if trace else cell.end_to_end
    out = {}
    for m in entries:
        v = load_module("metrics", m["name"], root).read(outcome.record)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def is_correct(outcome: Outcome) -> bool:
    """Every unit attempted came out whole, and every compared number is
    within its limit."""
    return (outcome.attempted > 0 and outcome.failed == 0
            and bool(outcome.checks) and all(c.ok for c in outcome.checks))


def result_line(cell: Cell, run: Run, outcome: Outcome,
                root: str = ROOT) -> dict:
    correct = is_correct(outcome)
    device = device_info(run.devices)
    device["memory_peak_bytes"] = outcome.memory_peak_bytes
    line = {"correct": correct, "attempted": outcome.attempted,
            "failed": outcome.failed,
            "metrics": metric_values(cell, outcome, run.trace, root),
            "device": device}
    if run.trace and outcome.trace is not None:
        device["busy_s"] = outcome.trace.busy_s
        device["window_s"] = outcome.trace.window_s
        line["breakdown"] = outcome.trace.breakdown()
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in outcome.checks}
    return line


def print_result(line: dict) -> None:
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})"
              f" {'ok' if c['value'] <= c['limit'] else 'FAILED'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


def now() -> float:
    return time.perf_counter()
