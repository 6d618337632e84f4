"""Unified telemetry (DESIGN.md §18): span tracing, counters, and the
search flight recorder — no-op by default; JAX is imported only when a
tracer is installed.

``repro.obs.trace``    — ``Tracer`` (nested spans, counters, gauges,
                         compile counts by span; installed, its spans
                         are ``jax.profiler`` annotations), the
                         process-global no-op default, Chrome trace-event
                         + flat metrics exporters.
``repro.obs.recorder`` — ``FlightRecorder``: one structured JSONL record
                         per search trial plus run header/footer.
``repro.obs.log``      — tiny level-filtered logger routed through the
                         tracer (instant events when tracing is on).
"""
from repro.obs.trace import (NULL_TRACER, Counters, NullTracer, Tracer,
                             get_tracer, set_tracer, use_tracer)
from repro.obs.recorder import FlightRecorder, load_run, read_records

__all__ = ["Tracer", "NullTracer", "NULL_TRACER", "Counters", "get_tracer",
           "set_tracer", "use_tracer", "FlightRecorder", "read_records",
           "load_run"]
