"""The search job on the CPU at a reduced size: a sound run is correct;
the control (the reference in float8 in the program's place) and each
fault planted in the timed path come out not correct under the committed
limits."""
import numpy as np
import pytest

import cells
from perfbench import harness

WAVE8 = "search.resnet18.wave8"


def _cell():
    return cells.cell(WAVE8, cells.small_resnet(),
                      dict(cells.SEARCH_TRAFFIC),
                      cells.committed_checks(WAVE8))


def test_a_sound_run_is_correct():
    out = cells.run(_cell(), seconds=0.5, trace=False)
    assert out.attempted >= cells.SEARCH_TRAFFIC["batch_size"]
    assert out.failed == 0
    assert harness.is_correct(out), out.checks
    rec = out.record
    assert rec["trials"] == out.attempted and rec["window_s"] >= 0.5
    assert rec["compiles_in_window"] == 0
    trials = harness.load_module("metrics", "trials_per_s").read(rec)
    assert trials == pytest.approx(rec["trials"] / rec["window_s"])
    rec = dict(rec, device_kind="TPU v5 lite")
    assert 0 < harness.load_module("metrics", "mfu.search").read(rec) < 100


def test_the_control_fails():
    import jax
    job = harness.load_module("jobs", "search")
    out = job.run(harness.Run(_cell(), 5, 0.3, False, harness.now(),
                              jax.devices(), control=True))
    limits = cells.committed_checks(WAVE8)
    assert any(out.control[k] > limits[k]["limit"] for k in limits), \
        out.control


def _fault_unpruned(monkeypatch):
    """The pruner hands the weights back unchanged."""
    from repro.core import pruning
    monkeypatch.setattr(pruning, "tile_prune",
                        lambda w, s, *a, **k: (w, 0.0 * s))


def _fault_half_wave(monkeypatch):
    """Half of each wave is left out: its rows repeat the other half's."""
    from repro.core.hass import CNNEvaluator
    orig = CNNEvaluator.evaluate_batch

    def half(self, xs):
        got = orig(self, list(xs[:len(xs) // 2]))
        return got + got[:len(xs) - len(got)]
    monkeypatch.setattr(CNNEvaluator, "evaluate_batch", half)


def _fault_answer(monkeypatch):
    """The hardware score is altered where it is produced."""
    from repro.core import hass
    orig = hass.frontier_hw_metrics

    def altered(ev, f):
        m = dict(orig(ev, f))
        m["thr"] *= 1.1
        return m
    monkeypatch.setattr(hass, "frontier_hw_metrics", altered)


@pytest.mark.parametrize("fault", [_fault_unpruned, _fault_half_wave,
                                   _fault_answer])
def test_a_fault_in_the_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = cells.run(_cell(), seconds=0.3)
    assert not harness.is_correct(out), out.checks


def test_the_window_closes_on_a_wave_boundary():
    job = harness.load_module("jobs", "search")
    closer = job.WindowCloser(deadline=0.0)
    closer.trial(x=np.zeros(2), metrics={}, round_size=2)
    with pytest.raises(job.WindowClosed):
        closer.trial(x=np.ones(2), metrics={}, round_size=2)
    assert len(closer.trials) == 2 and closer.t_close is not None
