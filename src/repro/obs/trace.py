"""Span tracing, counters, and gauges (DESIGN.md §18).

The process-global tracer defaults to ``NULL_TRACER``, whose ``enabled``
attribute is ``False`` — instrumented code guards every emission behind
``if tr.enabled`` or opens the shared no-op ``_NullSpan``, so the disabled
path pays one attribute check or one empty ``with`` and stays
IEEE-bit-identical to the pre-telemetry build. Instrumentation only
*reads* clocks and counters; it never touches a float any engine
computes, so the enabled path is bit-identical too (gated in
``benchmarks/obs_bench.py``).

While an enabled tracer is installed (``set_tracer``/``use_tracer``):

* every ``Tracer.span`` also opens a ``jax.profiler.TraceAnnotation`` of
  the same name, so under a running ``jax.profiler`` trace the span lands
  on the host plane, on its thread's line, stamped by the same clock as
  the device's events;
* every XLA compile (``/jax/core/compile/backend_compile_duration``, which
  a load from the persistent compile cache also emits) counts as
  ``compiles:<innermost open span>`` (the span part empty when none is
  open). The listener is registered on install and removed on uninstall.

The clock is injectable (``Tracer(clock=...)``) so tests run on fake time.
Exporters: ``to_chrome_trace``/``export_chrome_trace`` emit Chrome
trace-event JSON (``{"traceEvents": [...]}`` with "X" complete events —
loadable in Perfetto / ``chrome://tracing``); ``metrics``/
``export_metrics`` emit one flat JSON of counters and gauges.
"""
from __future__ import annotations

import json
import time
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional


class Counters:
    """A named-counter bag: plain dict-backed integer counters with
    snapshot/delta support. Backs ``DSECache.stats()`` and any other
    always-on counter set — increments are one dict store, cheap enough
    to leave unguarded on decision paths that already cost an array
    compare."""

    __slots__ = ("_c",)

    def __init__(self, *names: str):
        self._c: Dict[str, int] = {n: 0 for n in names}

    def inc(self, name: str, n: int = 1) -> None:
        self._c[name] = self._c.get(name, 0) + n

    def get(self, name: str) -> int:
        return self._c.get(name, 0)

    def set(self, name: str, value: int) -> None:
        self._c[name] = value

    def as_dict(self) -> Dict[str, int]:
        return dict(self._c)

    def snapshot(self) -> Dict[str, int]:
        return dict(self._c)

    def delta_since(self, snap: Dict[str, int]) -> Dict[str, int]:
        return {k: v - snap.get(k, 0) for k, v in self._c.items()}


class _NullSpan:
    """Shared no-op context manager handed out by ``NullTracer.span`` —
    one singleton, so the disabled path allocates nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class NullTracer:
    """The process-global default: every method is a no-op and ``enabled``
    is ``False``, so instrumented code pays one attribute check."""

    enabled = False

    def now(self) -> float:
        return time.perf_counter()

    def span(self, name: str, **args) -> _NullSpan:
        return _NULL_SPAN

    def add_span(self, name: str, t0: float, t1: float, depth: int = 0,
                 **args) -> None:
        pass

    def count(self, name: str, n: float = 1) -> None:
        pass

    def gauge(self, name: str, value: float) -> None:
        pass

    def instant(self, name: str, **args) -> None:
        pass


NULL_TRACER = NullTracer()


class _Span:
    """An open span: records its end time and pops itself off the tracer's
    stack on ``__exit__``. Exceptions propagate (the span still closes)."""

    __slots__ = ("_tr", "name", "t0", "depth", "args", "_ann")

    def __init__(self, tr: "Tracer", name: str, args: dict):
        self._tr = tr
        self.name = name
        self.args = args

    def __enter__(self) -> "_Span":
        tr = self._tr
        self._ann = None if tr._annotation is None \
            else tr._annotation(self.name)
        if self._ann is not None:
            self._ann.__enter__()
        self.depth = len(tr._stack)
        tr._stack.append(self)
        self.t0 = tr.now()
        return self

    def __exit__(self, *exc) -> bool:
        tr = self._tr
        t1 = tr.now()
        tr._stack.pop()
        tr._finish(self.name, self.t0, t1, self.depth, self.args)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class Tracer:
    """Collects nested spans, counters, and gauges in-process.

    * ``span(name, **args)`` — context manager; nesting depth comes from
      the tracer's open-span stack, start/end from its clock. Installed,
      the span is also a ``jax.profiler.TraceAnnotation``.
    * ``add_span(name, t0, t1, depth=0, **args)`` — record a span whose
      times the caller already measured (the search loop's per-trial
      ``trial`` span). Having no live extent, it never reaches a profiler
      trace.
    * ``count(name, n)`` / ``gauge(name, v)`` — monotonic counters and
      last-value gauges. ``instant(name, **args)`` records a
      zero-duration marker event.

    Events are appended as they finish, so a live span's parent is the
    first later live span one ``depth`` shallower.

    Timestamps are whatever the injected ``clock`` returns (seconds by
    default); the Chrome exporter scales to microseconds.
    """

    enabled = True

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self._clock = clock
        self.events: List[dict] = []     # finished spans + instants
        self._stack: List[_Span] = []
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        # jax.profiler.TraceAnnotation while installed, else None
        self._annotation = None

    # ----------------------------------------------------------------- #
    def now(self) -> float:
        return self._clock()

    def span(self, name: str, **args) -> _Span:
        return _Span(self, name, args)

    def add_span(self, name: str, t0: float, t1: float, depth: int = 0,
                 **args) -> None:
        self._finish(name, t0, t1, depth, args)

    def _finish(self, name: str, t0: float, t1: float, depth: int,
                args: dict) -> None:
        self.events.append({"name": name, "t0": t0, "t1": t1,
                            "depth": depth, "args": args})

    def instant(self, name: str, **args) -> None:
        t = self.now()
        self.events.append({"name": name, "t0": t, "t1": t,
                            "depth": len(self._stack), "args": args})

    def count(self, name: str, n: float = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    def gauge(self, name: str, value: float) -> None:
        self.gauges[name] = value

    # ----------------------------------------------------------------- #
    def to_chrome_trace(self, pid: int = 0, tid: int = 0) -> dict:
        """Chrome trace-event JSON: one "X" (complete) event per finished
        span, "i" (instant) for zero-duration markers; ``ts``/``dur`` in
        microseconds as the format requires. Loadable in Perfetto."""
        out = []
        for e in self.events:
            ts = e["t0"] * 1e6
            dur = (e["t1"] - e["t0"]) * 1e6
            ev = {"name": e["name"], "ph": "X", "ts": ts, "dur": dur,
                  "pid": pid, "tid": tid}
            if e["args"]:
                ev["args"] = dict(e["args"])
            out.append(ev)
        return {"traceEvents": out, "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path: str, pid: int = 0,
                            tid: int = 0) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(pid=pid, tid=tid), f, indent=1,
                      sort_keys=True)
        return path

    def metrics(self) -> dict:
        return {"counters": dict(self.counters),
                "gauges": dict(self.gauges)}

    def export_metrics(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.metrics(), f, indent=1, sort_keys=True)
        return path


# --------------------------------------------------------------------- #
# process-global tracer
# --------------------------------------------------------------------- #
_TRACER = NULL_TRACER
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_compile(event: str, duration: float, **_) -> None:
    if event == COMPILE_EVENT:
        stack = _TRACER._stack
        _TRACER.count("compiles:" + (stack[-1].name if stack else ""))


def get_tracer():
    """The process-global tracer (``NULL_TRACER`` unless installed)."""
    return _TRACER


def set_tracer(tracer) -> object:
    """Install ``tracer`` (or ``None`` for the no-op default) process-wide;
    returns the previous tracer so callers can restore it. Installing an
    enabled tracer turns on its profiler annotations and registers the
    compile listener; removing it undoes both."""
    global _TRACER
    prev = _TRACER
    new = NULL_TRACER if tracer is None else tracer
    if prev is new:
        return prev
    if prev.enabled:
        from jax import monitoring
        monitoring.unregister_event_duration_listener(_on_compile)
        prev._annotation = None
    _TRACER = new
    if new.enabled:
        import jax
        new._annotation = jax.profiler.TraceAnnotation
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
    return prev


@contextmanager
def use_tracer(tracer):
    """Install ``tracer`` for the duration of a ``with`` block."""
    prev = set_tracer(tracer)
    try:
        yield tracer
    finally:
        set_tracer(prev)
