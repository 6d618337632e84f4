"""Batched HASS search engine: TPE ask_batch/tell_batch and the
``hass_search(batch_size=...)`` frontier (DESIGN.md §8).

The contract: batch_size=1 replays the serial search trial-for-trial at a
fixed seed (the serial loop is the degenerate batch), larger batches cover
the same number of trials, and the TPE batch API is RNG-compatible with the
serial ask/tell stream.
"""
import numpy as np
import pytest

from repro.core.hass import hass_search
from repro.core.tpe import TPE


def _tpe(seed=0, dim=3):
    return TPE(lo=np.zeros(dim), hi=np.ones(dim), seed=seed)


def synth_eval(x):
    """Deterministic, hardware-free metric dict (isolates engine plumbing
    from jit numerics)."""
    return {"acc": float(np.cos(2.0 * x).mean()),
            "spa": float(np.mean(x)),
            "thr": 1.0 + float(np.sum(x)),
            "thr_norm": float(np.tanh(np.mean(x))),
            "dsp": float(np.mean(x) ** 2)}


class CountingBatchEval:
    """Per-proposal evaluate plus a batch hook, with call accounting."""

    def __init__(self):
        self.batch_calls = 0
        self.single_calls = 0

    def __call__(self, x):
        self.single_calls += 1
        return synth_eval(x)

    def evaluate_batch(self, xs):
        self.batch_calls += 1
        return [synth_eval(x) for x in xs]


# --------------------------------------------------------------------- #
# TPE batch API
# --------------------------------------------------------------------- #
def test_ask_batch_of_one_matches_serial_ask():
    a, b = _tpe(seed=5), _tpe(seed=5)
    for _ in range(15):
        (xa,) = a.ask_batch(1)
        xb = b.ask()
        assert np.array_equal(xa, xb)
        y = float(np.sum(xa))
        a.tell_batch([xa], [y])
        b.tell(xb, y)
    assert np.array_equal(a.best[0], b.best[0]) and a.best[1] == b.best[1]


def test_ask_batch_proposals_are_diverse_and_in_bounds():
    t = _tpe(seed=1, dim=4)
    for _ in range(12):          # past startup so the Parzen model is live
        x = t.ask()
        t.tell(x, float(-np.sum((x - 0.3) ** 2)))
    xs = t.ask_batch(8)
    assert len(xs) == 8
    flat = np.stack(xs)
    assert np.all(flat >= t.lo) and np.all(flat <= t.hi)
    assert len({tuple(np.round(x, 12)) for x in xs}) == 8


def test_tell_batch_length_mismatch_raises():
    t = _tpe()
    with pytest.raises(ValueError):
        t.tell_batch([np.zeros(3)], [1.0, 2.0])


# --------------------------------------------------------------------- #
# Constant-liar protocol (DESIGN.md §12)
# --------------------------------------------------------------------- #
def _seeded_model(seed=3, dim=4, n=14):
    t = _tpe(seed=seed, dim=dim)
    for _ in range(n):
        x = t.ask()
        t.tell(x, float(-np.sum((x - 0.4) ** 2)))
    return t


def test_constant_liar_batch_replays_at_fixed_seed():
    a = _seeded_model(seed=7)
    b = _seeded_model(seed=7)
    xa = a.ask_batch(6, liar="min")
    xb = b.ask_batch(6, liar="min")
    for p, q in zip(xa, xb):
        assert np.array_equal(p, q)


def test_constant_liar_leaves_observations_untouched():
    t = _seeded_model()
    xs_before = [x.copy() for x in t.xs]
    ys_before = list(t.ys)
    t.ask_batch(5, liar="min")
    assert len(t.xs) == len(xs_before) and t.ys == ys_before
    for p, q in zip(t.xs, xs_before):
        assert np.array_equal(p, q)


def test_constant_liar_preserves_rng_stream_position():
    """Model refits consume no RNG, so the draw AFTER a batch is identical
    whichever protocol proposed the batch — fixed-seed searches stay
    replayable across the liar knob."""
    a = _seeded_model(seed=11)
    b = _seeded_model(seed=11)
    a.ask_batch(5, liar="min")
    b.ask_batch(5, liar=None)
    assert np.array_equal(a.ask(), b.ask())


def test_constant_liar_changes_proposals_vs_independent():
    a = _seeded_model(seed=2)
    b = _seeded_model(seed=2)
    xs_l = a.ask_batch(6, liar="min")
    xs_i = b.ask_batch(6, liar=None)
    assert np.array_equal(xs_l[0], xs_i[0])      # first member: same model
    assert any(not np.array_equal(p, q) for p, q in zip(xs_l[1:], xs_i[1:]))


def test_constant_liar_single_member_is_plain_ask():
    a = _seeded_model(seed=5)
    b = _seeded_model(seed=5)
    (xa,) = a.ask_batch(1, liar="min")
    assert np.array_equal(xa, b.ask())


def test_constant_liar_startup_batch_matches_legacy():
    a = _tpe(seed=9)
    b = _tpe(seed=9)
    a.tell(np.full(3, 0.5), 1.0)     # 1 obs, still pre-startup
    b.tell(np.full(3, 0.5), 1.0)
    xs_l = a.ask_batch(4, liar="min")
    xs_i = b.ask_batch(4, liar=None)
    for p, q in zip(xs_l, xs_i):
        assert np.array_equal(p, q)


def test_unknown_liar_mode_raises():
    with pytest.raises(ValueError):
        _tpe().ask_batch(3, liar="median")


def test_hass_search_passes_liar_through():
    kw = dict(iters=18, seed=6, batch_size=5)
    r_l = hass_search(synth_eval, 4, liar="min", **kw)
    r_i = hass_search(synth_eval, 4, liar=None, **kw)
    assert len(r_l.trials) == len(r_i.trials) == 18
    # post-startup rounds diverge between protocols
    assert any(not np.array_equal(a.x, b.x)
               for a, b in zip(r_l.trials[10:], r_i.trials[10:]))


# --------------------------------------------------------------------- #
# Batched hass_search
# --------------------------------------------------------------------- #
def test_batch_size_one_reproduces_serial_search_trial_for_trial():
    kw = dict(iters=24, seed=9, s_max=0.9)
    serial = hass_search(synth_eval, 4, **kw)
    batched = hass_search(synth_eval, 4, batch_size=1, **kw)
    assert len(serial.trials) == len(batched.trials) == 24
    for ts, tb in zip(serial.trials, batched.trials):
        assert np.array_equal(ts.x, tb.x)
        assert ts.score == tb.score
        assert ts.metrics == tb.metrics
    assert serial.best_score == batched.best_score
    assert np.array_equal(serial.best_x, batched.best_x)


def test_batched_search_uses_evaluate_batch_and_covers_all_trials():
    ev = CountingBatchEval()
    r = hass_search(ev, 4, iters=20, seed=0, batch_size=6)
    assert len(r.trials) == 20
    assert ev.batch_calls == 4          # ceil(20/6) rounds: 6+6+6+2
    assert ev.single_calls == 0
    assert r.best_score == max(t.score for t in r.trials)
    # running_best stays monotone across batch boundaries
    rb = r.running_best("score")
    assert all(b >= a - 1e-12 for a, b in zip(rb, rb[1:]))


def test_batched_search_falls_back_to_per_proposal_evaluate():
    calls = []

    def ev(x):
        calls.append(x)
        return synth_eval(x)

    r = hass_search(ev, 3, iters=10, seed=2, batch_size=4)
    assert len(r.trials) == 10 and len(calls) == 10


def test_batched_search_rejects_bad_batch_size():
    with pytest.raises(ValueError):
        hass_search(synth_eval, 3, iters=4, batch_size=0)


def test_hardware_aware_flag_respected_in_batched_scores():
    kw = dict(iters=16, seed=4, batch_size=5)
    hw = hass_search(synth_eval, 3, hardware_aware=True, **kw)
    sw = hass_search(synth_eval, 3, hardware_aware=False, **kw)
    for t in sw.trials:
        m = t.metrics
        assert t.score == pytest.approx(m["acc"] + 0.3 * m["spa"])
    for t in hw.trials:
        m = t.metrics
        assert t.score == pytest.approx(
            m["acc"] + 0.3 * m["spa"] + 0.5 * m["thr_norm"] - 0.3 * m["dsp"])


def test_batched_cnn_search_splits_evaluate_into_forward_and_dse():
    """Each wave's ``evaluate`` span holds exactly one ``evaluate.forward``
    (the vmapped prune+forward until host arrays) and one ``evaluate.dse``
    (the batched DSE), and tracing leaves the transcript bit-identical."""
    import jax
    from repro.configs import reduce_config
    from repro.configs.paper_cnns import RESNET18
    from repro.core.hass import CNNEvaluator
    from repro.core.perf_model import TPUModel
    from repro.models import cnn
    from repro.obs import Tracer, use_tracer

    cfg = reduce_config(RESNET18)
    rng = jax.random.PRNGKey(0)
    params = cnn.init_params(cfg, rng)
    images = jax.random.normal(rng, (4, cfg.img_res, cfg.img_res, 3))
    tpu = TPUModel()
    ev = CNNEvaluator(cfg, params, images, tpu, budget=tpu.chip_budget,
                      dse_iters=60)
    L = len(ev.prunable)
    kw = dict(iters=8, s_max=0.9, seed=3, batch_size=4, liar="min")
    off = hass_search(ev, L, **kw)
    with use_tracer(Tracer()) as tr:
        on = hass_search(ev, L, **kw)
    assert len(on.trials) == len(off.trials) == 8
    for a, b in zip(off.trials, on.trials):
        assert np.array_equal(a.x, b.x)
        assert a.score == b.score and a.metrics == b.metrics
    assert on.best_score == off.best_score

    # events are appended as they finish: each wave's two children, one
    # level deeper, come right before their ``evaluate``
    names = [(e["name"], e["depth"]) for e in tr.events
             if e["name"].startswith("evaluate")]
    assert names == [("evaluate.forward", 1), ("evaluate.dse", 1),
                     ("evaluate", 0)] * 2      # two waves of four
    evs = [e for e in tr.events if e["name"].startswith("evaluate")]
    for fwd, dse, outer in zip(evs[::3], evs[1::3], evs[2::3]):
        assert outer["t0"] <= fwd["t0"] <= fwd["t1"] <= dse["t0"] \
            <= dse["t1"] <= outer["t1"]
