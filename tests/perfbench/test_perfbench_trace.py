"""Trace reduction (perfbench/trace_reduce.py) on a trace recorded on a
TPU v5e: one evaluator wave, annotated 'probe.wave'."""
import gzip
import os

import pytest

import cells  # noqa: F401  (puts the repo root on sys.path)
from perfbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "v5e_wave.xplane.textproto.gz")


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    with gzip.open(DATA, "rt") as f:
        return ProfileData.from_text_proto(f.read())


@pytest.fixture(scope="module")
def summary(profile):
    return tr.reduce(profile, "probe.wave")


def test_union_length_merges_overlaps_and_clips():
    total, gaps = tr.union_length([(0, 4), (2, 6), (8, 9), (12, 20)], 1, 15)
    assert total == (6 - 1) + (9 - 8) + (15 - 12)
    assert gaps == [(6, 8), (9, 12)]


def test_union_length_of_nothing_is_one_gap():
    assert tr.union_length([], 0, 5) == (0.0, [(0, 5)])


@pytest.mark.parametrize("name,label", [
    ("%fusion.150 = (f32[4,8]{0,1:T(8,128)S(1)}, bf16[4]) fusion(f32[4]), "
     "kind=kLoop", "fusion.150 fusion"),
    ("%copy.57 = bf16[1,1024]{1,0:T(8,128)(2,1)S(1)} copy(bf16[1,1024] %x)",
     "copy.57 copy"),
    ("plain", "plain"),
])
def test_op_label_keeps_instruction_and_opcode(name, label):
    assert tr.op_label(name) == label


def test_module_base_drops_the_fingerprint():
    assert tr.module_base("jit__lambda(16709884215922222397)") == \
        "jit__lambda"


def test_window_is_the_annotation(summary):
    assert summary.window_s == pytest.approx(27.930180e-3)


def test_busy_is_the_union_of_the_operations(profile, summary):
    lo, hi = summary.window_ns
    plane = next(p for p in profile.planes if p.name == "/device:TPU:0")
    ops = next(l for l in plane.lines if l.name == tr.OPS_LINE)
    marks = sorted({(max(e.start_ns, lo), min(e.start_ns + e.duration_ns,
                                               hi)) for e in ops.events})
    covered, end = 0.0, lo
    for a, b in marks:                      # a second, plainer sweep
        if b > end:
            covered += b - max(a, end)
            end = b
    assert summary.busy_s == pytest.approx(covered * 1e-9, rel=1e-9)
    assert 0.0 < summary.idle_share < 1.0


def test_program_time_is_summed_by_name(summary):
    runs, seconds = summary.module_seconds("jit__eval")
    assert runs == 1
    assert seconds == pytest.approx(9.842809e-3, rel=1e-6)
    assert summary.module_seconds("no_such_program") == (0, 0)


def test_breakdown_lists_the_largest_first(summary):
    b = summary.breakdown()
    for key in ("device_ops", "idle_gaps"):
        vals = [v for _, v in b[key]]
        assert 0 < len(vals) <= tr.TOP
        assert vals == sorted(vals, reverse=True)
    assert sum(summary.gap_seconds.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-6)


def test_program_spans_move_onto_the_trace_clock(profile, summary):
    lo, hi = summary.window_ns
    plane = next(p for p in profile.planes if p.name == "/device:TPU:0")
    ops = next(l for l in plane.lines if l.name == tr.OPS_LINE)
    _, gaps = tr.union_length([(e.start_ns, e.start_ns + e.duration_ns)
                               for e in ops.events], lo, hi)
    a, b = max(gaps, key=lambda g: g[1] - g[0])
    mid_s = 100.0 + ((a + b) / 2 - lo) * 1e-9
    # a program span around the largest gap's midpoint, on perf_counter
    # seconds with the window opening at 100 s: the innermost cover there
    spans = [("evaluate", mid_s - 1e-9, mid_s + 1e-9)]
    s = tr.reduce(profile, "probe.wave", host_spans=spans,
                  window_start_s=100.0)
    assert s.gap_seconds["evaluate"] == pytest.approx((b - a) * 1e-9)


def test_a_missing_window_is_an_error(profile):
    with pytest.raises(ValueError, match="no host annotation"):
        tr.reduce(profile, "perfbench.window")


def test_only_the_longest_gaps_are_charged_one_by_one(profile, monkeypatch):
    full = tr.reduce(profile, "probe.wave")
    monkeypatch.setattr(tr, "CHARGED_GAPS", 3)
    s = tr.reduce(profile, "probe.wave")
    assert tr.SHORT_GAPS in s.gap_seconds
    assert sum(s.gap_seconds.values()) == pytest.approx(
        sum(full.gap_seconds.values()), rel=1e-9)
