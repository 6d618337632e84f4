"""The evaluator's span metrics: each reader on a synthetic record, and the
search job's traced path on the CPU (the profiler left out) records the
spans the readers look for."""
import pytest

import cells
from perfbench import harness

WAVE8 = "search.resnet18.wave8"
READERS = [("search.forward_ms_per_trial", "evaluate.forward"),
           ("search.dse_ms_per_trial", "evaluate.dse")]


@pytest.mark.parametrize("metric,span", READERS)
def test_reader_gives_ms_per_trial(metric, span):
    rec = {"trials": 80, "span_s": {"evaluate": 0.5, span: 0.2}}
    got = harness.load_module("metrics", metric).read(rec)
    assert got == pytest.approx(0.2 / 80 * 1e3)


@pytest.mark.parametrize("metric,span", READERS)
@pytest.mark.parametrize("rec", [
    {"trials": 80, "span_s": {"evaluate": 0.5}},      # the parent's spans
    {"trials": 80},                                   # an untraced record
    {"trials": 0, "span_s": {"evaluate.forward": 0.1,
                             "evaluate.dse": 0.1}},
])
def test_reader_gives_none_without_its_span(metric, span, rec):
    assert harness.load_module("metrics", metric).read(rec) is None


def test_traced_search_records_the_evaluator_spans(monkeypatch):
    real = harness.Profiler
    monkeypatch.setattr(harness, "Profiler", lambda enabled: real(False))
    cell = cells.cell(WAVE8, cells.small_resnet(), dict(cells.SEARCH_TRAFFIC),
                      cells.committed_checks(WAVE8))
    rec = cells.run(cell, seconds=0.3, trace=True).record
    got = {m: harness.load_module("metrics", m).read(rec)
           for m in ("search.evaluate_ms_per_trial",
                     "search.forward_ms_per_trial",
                     "search.dse_ms_per_trial")}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["search.forward_ms_per_trial"] \
        + got["search.dse_ms_per_trial"] \
        <= got["search.evaluate_ms_per_trial"]
