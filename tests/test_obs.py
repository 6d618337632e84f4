"""Observability (DESIGN.md §18): tracer/recorder invisibility and the
flight-recorder + report-tool contracts.

The heavy end-to-end gates (real-evaluator bit-identity, wall-clock
overhead, Chrome-trace schema) live in ``benchmarks/obs_bench.py``; this
file keeps the cheap invariants in tier-1 with a fake evaluator and a
fake clock.
"""
import io
import json
import os
import sys

import numpy as np
import pytest

from repro.core.hass import hass_search
from repro.obs import (FlightRecorder, NULL_TRACER, Tracer, get_tracer,
                       load_run, read_records, set_tracer, use_tracer)
from repro.obs.log import capture, get_logger

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(ROOT, "tools"))

import trace_report  # noqa: E402


class FakeCache:
    """Quacks like a DSECache for the recorder's counter snapshots."""

    def __init__(self):
        self.calls = 0

    def stats(self):
        return {"hits": 2 * self.calls, "warm_l1": self.calls,
                "warm_l2": 0, "cold_runs": self.calls,
                "warm_hits": self.calls}


class FakeEval:
    """Deterministic metric function of x — cheap stand-in for the
    jit-backed evaluators."""

    def __init__(self):
        self.dse_cache = FakeCache()

    def __call__(self, x):
        self.dse_cache.calls += 1
        x = np.asarray(x)
        return {"acc": float(np.mean(np.cos(3.0 * x))),
                "spa": float(np.mean(x)),
                "thr": 1.0 + float(x[0]), "dsp": 0.5}


def _run(seed=0, iters=8, recorder=None):
    return hass_search(FakeEval(), 4, iters=iters, seed=seed,
                       hardware_aware=False, include_act=False,
                       recorder=recorder)


def _assert_identical(a, b):
    assert len(a.trials) == len(b.trials)
    for ta, tb in zip(a.trials, b.trials):
        assert np.array_equal(ta.x, tb.x)
        assert ta.score == tb.score and ta.metrics == tb.metrics
    assert a.best_score == b.best_score


def test_default_tracer_is_disabled_null():
    tr = get_tracer()
    assert tr is NULL_TRACER and tr.enabled is False
    with tr.span("anything", k=1):
        tr.count("x")
        tr.gauge("y", 2.0)                  # all no-ops


def test_noop_and_enabled_tracers_leave_transcript_bit_identical(tmp_path):
    ref = _run()
    off = _run()                            # NULL tracer (the default)
    with use_tracer(Tracer()):
        with FlightRecorder(str(tmp_path / "run.jsonl")) as rec:
            on = _run(recorder=rec)
    _assert_identical(ref, off)
    _assert_identical(ref, on)
    assert get_tracer() is NULL_TRACER      # use_tracer restored


def test_fake_clock_span_nesting_and_attribution():
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    tr = Tracer(clock=clock)
    with tr.span("outer", job="a"):         # t0=1
        with tr.span("inner"):              # t0=2, t1=3
            pass
    # inner finishes first; depth reflects stack position at entry
    inner, outer = tr.events
    assert inner["name"] == "inner" and inner["depth"] == 1
    assert inner["t0"] == 2.0 and inner["t1"] == 3.0
    assert outer["name"] == "outer" and outer["depth"] == 0
    assert outer["t0"] == 1.0 and outer["t1"] == 4.0
    assert outer["args"] == {"job": "a"}
    doc = tr.to_chrome_trace()
    ev = {e["name"]: e for e in doc["traceEvents"]}
    assert ev["outer"]["ph"] == "X"
    assert ev["outer"]["ts"] == 1e6 and ev["outer"]["dur"] == 3e6
    assert ev["inner"]["ts"] == 2e6 and ev["inner"]["dur"] == 1e6


def test_tracer_counters_gauges_histograms():
    tr = Tracer()
    tr.count("n")
    tr.count("n", 4)
    tr.gauge("g", 2.5)
    m = tr.metrics()
    assert m["counters"]["n"] == 5
    assert m["gauges"]["g"] == 2.5
    assert set(m) == {"counters", "gauges"}     # no histograms


def _parent(events, i):
    """The parent of live span ``events[i]``: the first later event one
    depth shallower (events are appended as they finish)."""
    d = events[i]["depth"]
    if d == 0:
        return None
    return next(j for j in range(i + 1, len(events))
                if events[j]["depth"] == d - 1)


def test_spans_record_their_parent():
    """Spans record their nesting: depth plus finish order names each
    live span's parent, whose interval holds the child's."""
    t = iter(range(1, 100))
    tr = Tracer(clock=lambda: float(next(t)))
    with tr.span("a"):
        with tr.span("b"):
            with tr.span("c"):
                pass
            tr.instant("mark")
        with tr.span("d"):
            pass
    ev = tr.events
    by = {e["name"]: i for i, e in enumerate(ev)}
    parent = {e["name"]: _parent(ev, i) for i, e in enumerate(ev)}
    assert parent == {"c": by["b"], "mark": by["b"], "b": by["a"],
                      "d": by["a"], "a": None}
    for i, e in enumerate(ev):
        p = parent[e["name"]]
        if p is not None:
            assert ev[p]["t0"] < e["t0"] <= e["t1"] < ev[p]["t1"]


def _xplane(log_dir):
    import glob
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return path


def test_installed_spans_land_on_the_profiler_host_plane(tmp_path):
    import jax
    from jax.profiler import ProfileData
    with use_tracer(Tracer()) as tr:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tr.span("obs.outer"):
                with tr.span("obs.inner"):
                    sum(range(20000))
        finally:
            jax.profiler.stop_trace()
    with tr.span("obs.uninstalled"):        # a plain tracer annotates nothing
        pass
    prof = ProfileData.from_file(_xplane(str(tmp_path)))
    (host,) = [p for p in prof.planes if p.name == "/host:CPU"]
    got = {}
    for line in host.lines:
        for e in line.events:
            if e.name.startswith("obs."):
                got.setdefault(e.name, []).append(
                    (line.name, e.start_ns, e.start_ns + e.duration_ns))
    assert set(got) == {"obs.outer", "obs.inner"}
    ((line_o, o0, o1),), ((line_i, i0, i1),) = got["obs.outer"], \
        got["obs.inner"]
    assert line_o == line_i                 # the same thread's line
    assert o0 <= i0 < i1 <= o1


def test_compiles_are_counted_by_innermost_span():
    import jax
    import jax.numpy as jnp
    x = jnp.arange(7.0)
    f = jax.jit(lambda v: v * 3.0 + 1.0)
    with use_tracer(Tracer()) as tr:
        with tr.span("outer"):
            with tr.span("first"):
                f(x).block_until_ready()
            n = dict(tr.counters)
            with tr.span("second"):
                f(x).block_until_ready()
    assert n.get("compiles:first") == 1
    assert "compiles:outer" not in n
    assert "compiles:second" not in tr.counters
    assert tr.counters == n                 # the second call compiled nothing


def test_no_monitoring_listener_outlives_use_tracer():
    from jax._src import monitoring
    from repro.obs import trace as obs_trace

    def ours():
        return monitoring.get_event_duration_listeners().count(
            obs_trace._on_compile)

    assert ours() == 0
    with use_tracer(Tracer()):
        assert ours() == 1
        with use_tracer(Tracer()):          # nested: still one
            assert ours() == 1
        assert ours() == 1
    assert ours() == 0
    with use_tracer(NULL_TRACER):
        assert ours() == 0


def test_flight_recorder_roundtrip_and_footer_sums(tmp_path):
    path = str(tmp_path / "run.jsonl")
    with FlightRecorder(path) as rec:
        r = _run(recorder=rec)
    # every line re-parses as JSON
    with open(path) as f:
        lines = [json.loads(s) for s in f if s.strip()]
    assert lines[0]["record"] == "header"
    assert lines[0]["search"] == "hass_search"
    assert lines[-1]["record"] == "footer"
    assert read_records(path) == lines
    run = load_run(path)
    assert len(run["trials"]) == len(r.trials) == run["footer"]["n_trials"]
    assert run["footer"]["best_score"] == r.best_score
    # footer totals equal the sum of per-trial records, field for field
    for field in ("cache", "engine", "phases"):
        tot = {}
        for t in run["trials"]:
            for k, v in t[field].items():
                tot[k] = tot.get(k, 0) + v
        for k, v in run["footer"]["totals"][field].items():
            assert v == pytest.approx(tot.get(k, 0), rel=1e-9, abs=1e-12)
    # trial records carry the recorded proposal and score verbatim
    for t, trial in zip(run["trials"], r.trials):
        assert t["x"] == list(trial.x)
        assert t["score"] == trial.score


def test_trace_report_diff_same_and_divergent(tmp_path):
    paths = {}
    for tag, seed in (("a", 0), ("b", 0), ("c", 1)):
        p = str(tmp_path / f"{tag}.jsonl")
        with FlightRecorder(p) as rec:
            _run(seed=seed, recorder=rec)
        paths[tag] = p
    buf = io.StringIO()
    same = trace_report.diff_runs(trace_report.load_run(paths["a"]),
                                  trace_report.load_run(paths["b"]),
                                  out=buf)
    assert same == 0
    assert "0 trials" in buf.getvalue()
    buf = io.StringIO()
    cross = trace_report.diff_runs(trace_report.load_run(paths["a"]),
                                   trace_report.load_run(paths["c"]),
                                   out=buf)
    assert cross > 0
    assert "phase deltas" in buf.getvalue()
    buf = io.StringIO()
    trace_report.summarize(trace_report.load_run(paths["a"]), out=buf)
    out = buf.getvalue()
    assert "hass_search" in out and "phases" in out


def test_trace_report_survives_missing_footer(tmp_path):
    p = str(tmp_path / "crashed.jsonl")
    with FlightRecorder(p) as rec:
        _run(recorder=rec)
    lines = open(p).read().splitlines()
    with open(p, "w") as f:                 # drop the footer: a killed run
        f.write("\n".join(lines[:-1]) + "\n")
    run = trace_report.load_run(p)
    assert run["footer"] is None
    tot = trace_report.totals_of(run)
    assert sum(tot["phases"].values()) > 0


def test_logger_level_filter_and_capture():
    log = get_logger("obs-test")
    with capture("obs-test") as lines:
        log.debug("too quiet")
        log.info("hello")
        log.error("bad")
    assert lines == ["[obs-test] hello", "[obs-test] bad"]
    with use_tracer(Tracer()) as tr:
        with capture("obs-test") as lines:
            log.warning("traced")
        assert tr.metrics()["counters"]["log.obs-test.warning"] == 1


def test_engine_dispatch_counters_track_dse_runs():
    from repro.core.dse import (engine_dispatch_stats, incremental_dse,
                                reset_engine_dispatch)
    from repro.core.perf_model import FPGAModel, LayerCost

    layers = [LayerCost(f"l{i}", macs=4096 * (i + 1), m_dot=64,
                        weight_count=4096, act_in=1, act_out=1)
              for i in range(3)]
    reset_engine_dispatch()
    before = engine_dispatch_stats()
    assert all(v == 0 for v in before.values())
    incremental_dse(layers, FPGAModel(), budget=512)
    after = engine_dispatch_stats()
    assert sum(after.values()) >= 1         # some engine was dispatched
    reset_engine_dispatch()


def test_search_counters_published_when_enabled():
    with use_tracer(Tracer()) as tr:
        _run()
    m = tr.metrics()
    assert m["counters"]["search.trials"] == 8
    assert m["gauges"]["search.dse_cache.cold_runs"] > 0
    spans = [e for e in tr.events if e["name"] == "trial"]
    assert len(spans) == 8
