"""The serve job on the CPU at a reduced size: a sound run is correct;
the control (the reference in float8 in the program's place) and each
fault planted in the timed path come out not correct under the committed
limits."""
import pytest

import cells
from perfbench import harness

DECODE = "serve.qwen3-0.6b.decode"


def _cell():
    return cells.cell(DECODE, cells.mid_qwen3(),
                      dict(cells.MID_SERVE_TRAFFIC),
                      cells.committed_checks(DECODE))


def test_a_sound_run_is_correct():
    out = cells.run(_cell(), seconds=0.5)
    assert harness.is_correct(out), out.checks
    rec = out.record
    t = cells.MID_SERVE_TRAFFIC
    assert out.attempted == rec["calls"] * t["batch_slots"]
    assert rec["output_tokens"] == out.attempted * t["max_new"]
    rec = dict(rec, device_kind="TPU v5 lite")
    assert 0 < harness.load_module("metrics", "mfu.serve").read(rec) < 100


def test_the_control_fails():
    import jax
    job = harness.load_module("jobs", "serve")
    out = job.run(harness.Run(_cell(), 5, 0.3, False, harness.now(),
                              jax.devices(), control=True))
    limit = cells.committed_checks(DECODE)["served_logit_gap"]["limit"]
    assert out.control["served_logit_gap"] > limit, out.control


def _wrap_decode(monkeypatch, change):
    from repro.models import transformer as tfm
    orig = tfm.decode_step

    def step(cfg, params, cache, token):
        logits, new = orig(cfg, params, cache, token)
        return change(cache, logits, new)
    monkeypatch.setattr(tfm, "decode_step", step)


def _fault_state_unchanged(monkeypatch):
    """The decode step hands back the cache it was given."""
    _wrap_decode(monkeypatch, lambda old, logits, new: (logits, old))


def _fault_half_batch(monkeypatch):
    """Half of the batch is left out: its rows repeat the other half's."""
    def change(old, logits, new):
        h = logits.shape[0] // 2
        return logits.at[h:].set(logits[:h]), new
    _wrap_decode(monkeypatch, change)


def _fault_token(monkeypatch):
    """A token is altered where it is sampled: every request's third."""
    from repro.serve.serve_loop import ServeSession
    orig = ServeSession._sample
    calls = []

    def sample(self, logits):
        tok = orig(self, logits)
        calls.append(1)
        if len(calls) % cells.MID_SERVE_TRAFFIC["max_new"] == 3:
            tok = (tok + 1) % logits.shape[-1]
        return tok
    monkeypatch.setattr(ServeSession, "_sample", sample)


def test_the_sample_reads_every_part_of_the_batch():
    job = harness.load_module("jobs", "serve")
    t = cells.MID_SERVE_TRAFFIC
    for seed in (1, 2 ** 40 + 3):
        sel = job.check_sample(seed, 5, t["batch_slots"], t["check_requests"])
        slots = sorted(r for _, r in sel)
        blocks = {r * t["check_requests"] // t["batch_slots"] for r in slots}
        assert blocks == set(range(t["check_requests"]))
        assert all(0 <= c < 5 for c, _ in sel)


@pytest.mark.parametrize("fault", [_fault_state_unchanged,
                                   _fault_half_batch, _fault_token])
def test_a_fault_in_the_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = cells.run(_cell(), seconds=0.3)
    assert not harness.is_correct(out), out.checks


def test_the_qwen3_family_gives_the_weights_of_weights_lm_params():
    import jax
    import numpy as np
    from perfbench import weights
    cfg = cells.small_qwen3()
    key = weights.seed_key(2 ** 40 + 9)
    got = harness.load_module("reference", "qwen3").params(cfg, key)
    want = weights.lm_params(cfg, key)
    assert jax.tree_util.tree_structure(got) \
        == jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_the_qwen3_family_counts_the_flops_of_costs_request_flops():
    from perfbench import costs
    cfg = harness.load_cell(DECODE).config
    t = cells._json("perfbench/traffic/decode.json")
    fam = harness.load_module("families", cfg["family"])
    P, N = t["prompt_len"], t["max_new"]
    assert fam.request_flops(cfg, P, N) == costs.request_flops(cfg, P, N)


def test_traced_serve_records_the_program_spans_and_counters(monkeypatch):
    real = harness.Profiler
    monkeypatch.setattr(harness, "Profiler", lambda enabled: real(False))
    cell = cells.cell(DECODE, cells.small_qwen3(), dict(cells.SERVE_TRAFFIC),
                      cells.committed_checks(DECODE))
    rec = cells.run(cell, seconds=0.3, trace=True).record
    steps = rec["calls"] * (cells.SERVE_TRAFFIC["max_new"] - 1)
    assert rec["span_s"]["serve.prefill"] > 0
    assert rec["span_s"]["serve.decode"] > 0
    assert rec["counters"]["serve.decode_steps"] == steps
    rec = cells.run(cell, seconds=0.3, trace=False).record
    assert rec["span_s"] == {} and rec["counters"] == {}


def test_an_untied_head_adds_its_own_leaf_and_keeps_the_tied_weights():
    import jax
    import numpy as np
    from perfbench import weights
    cfg = cells.small_qwen3()
    key = weights.seed_key(2 ** 40 + 11)
    tied = weights.lm_params(cfg, key)
    untied = weights.lm_params(dict(cfg, tie_word_embeddings=False), key)
    head = untied.pop("lm_head")
    assert head.shape == (cfg["hidden_size"], cfg["vocab_size"])
    assert not np.allclose(head, tied["embed"].T)
    for a, b in zip(jax.tree_util.tree_leaves(tied),
                    jax.tree_util.tree_leaves(untied)):
        assert np.array_equal(a, b)


def test_span_seconds_sums_each_span_name():
    class T:
        events = [{"name": "a", "t0": 1.0, "t1": 1.5},
                  {"name": "b", "t0": 2.0, "t1": 2.25},
                  {"name": "a", "t0": 3.0, "t1": 3.5}]
    assert harness.span_seconds(T()) == {"a": 1.0, "b": 0.25}
    assert harness.span_seconds(None) == {}
