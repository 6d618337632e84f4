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
