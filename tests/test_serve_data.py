"""Serving session + data pipeline."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.data.pipeline import DataPipeline
from repro.configs.base import ShapeConfig
from repro.models import build_model
from repro.serve.serve_loop import Request, ServeSession

CFG = reduce_config(get_config("qwen3-0.6b"))
RNG = jax.random.PRNGKey(0)


def test_serve_session_matches_manual_greedy():
    api = build_model(CFG)
    params = api.init(RNG)
    prompts = [np.arange(8) % CFG.vocab_size for _ in range(2)]
    sess = ServeSession(api, params, batch_slots=2, S_max=32)
    outs = sess.generate(prompts, max_new=5)
    assert len(outs) == 2 and all(len(o) == 5 for o in outs)

    # manual greedy
    toks = jnp.asarray(np.stack(prompts), jnp.int32)
    logits, cache = api.prefill(params, toks, 32)
    cur = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    manual = [np.asarray(cur)]
    for _ in range(4):
        logits, cache = api.decode_step(params, cache, cur)
        cur = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        manual.append(np.asarray(cur))
    manual = np.concatenate(manual, axis=1)
    assert outs == [list(map(int, r)) for r in manual]


def _eager_greedy(api, params, prompts, max_new, S_max):
    """Greedy tokens, and the last-position logits each was sampled from,
    from the model's prefill and a plain eager loop of ``decode_step``: no
    jit, no donation, every cache a fresh value."""
    lens = [len(p) for p in prompts]
    toks = np.zeros((len(prompts), max(lens)), np.int32)
    for j, p in enumerate(prompts):
        toks[j, :len(p)] = p
    kw = {} if min(lens) == max(lens) else \
        {"prompt_lens": jnp.asarray(lens, jnp.int32)}
    logits, cache = api.prefill(params, jnp.asarray(toks), S_max, **kw)
    out, seen = [], []
    for step in range(max_new):
        seen.append(np.asarray(logits[:, -1]))
        cur = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        out.append(np.asarray(cur))
        if step < max_new - 1:
            logits, cache = api.decode_step(params, cache, cur)
    return np.concatenate(out, axis=1).tolist(), np.stack(seen)


class _LogitsSession(ServeSession):
    """A ``ServeSession`` that keeps the last-position logits of every
    batch it samples from."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.seen = []

    def _sample(self, logits):
        self.seen.append(np.asarray(logits[:, -1]))
        return super()._sample(logits)


# (arch, prompt lengths, max_new, S_max, driver)
DONATED_DECODE_CASES = {
    "ragged": ("qwen3-0.6b", (9, 4, 7), 6, 16, "generate"),
    # reduced Mixtral has an 8-slot window: positions 4..15 wrap the ring
    "window_ring_wrap": ("mixtral-8x7b", (6, 4), 12, 32, "generate"),
    "mla": ("deepseek-v3-671b", (5, 3), 5, 16, "generate"),
    # two admission groups decode in turn, each on its own donated cache
    "open_loop_two_groups": ("qwen3-0.6b", (6, 6, 6, 6), 16, 32,
                             "open_loop"),
}


@pytest.mark.parametrize("case", list(DONATED_DECODE_CASES))
def test_donated_decode_matches_eager_loop(case):
    """``ServeSession``'s jitted decode step, which takes its cache donated
    and writes one row per sequence per layer into it, serves the same
    greedy tokens as an eager, undonated ``decode_step`` loop, from the
    same logits (float32, so that the two differ by rounding alone)."""
    arch, lens, max_new, S_max, driver = DONATED_DECODE_CASES[case]
    cfg = dataclasses.replace(reduce_config(get_config(arch)),
                              dtype="float32")
    api = build_model(cfg)
    params = api.init(RNG)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, size=n) for n in lens]
    sess = _LogitsSession(api, params, batch_slots=len(prompts),
                          S_max=S_max)
    if driver == "generate":
        ref, ref_logits = _eager_greedy(api, params, prompts, max_new, S_max)
        assert sess.generate(prompts, max_new=max_new) == ref
        got = np.stack(sess.seen)
        tol = 1e-4 * max(float(np.abs(ref_logits).max()), 1.0)
        assert float(np.abs(got - ref_logits).max()) <= tol
        return
    half = len(prompts) // 2
    reqs = [Request(prompt=p, max_new=max_new, arrival=0.0 if j < half
                    else 5.0) for j, p in enumerate(prompts)]
    rep = sess.serve_open_loop(reqs, step_cycles=1.0, prefill_cycles=1.0)
    first, second = reqs[:half], reqs[half:]
    adm = rep.admissions
    assert len(set(adm[:half])) == len(set(adm[half:])) == 1
    # the second group joins while the first still decodes
    assert adm[half] < rep.completions[:half].min()
    for group in (first, second):
        ref, _ = _eager_greedy(api, params, [r.prompt for r in group],
                               max_new, S_max)
        assert [r.out for r in group] == ref


def test_serve_batching_chunks_requests():
    api = build_model(CFG)
    params = api.init(RNG)
    prompts = [np.arange(6) for _ in range(5)]
    sess = ServeSession(api, params, batch_slots=2, S_max=16)
    outs = sess.generate(prompts, max_new=3)
    assert len(outs) == 5


def test_generate_emits_one_prefill_and_one_decode_span_per_chunk():
    from repro.obs import Tracer, use_tracer
    api = build_model(CFG)
    params = api.init(RNG)
    prompts = [np.arange(6) for _ in range(5)]
    sess = ServeSession(api, params, batch_slots=2, S_max=16)
    off = sess.generate(prompts, max_new=4)
    with use_tracer(Tracer()) as tr:
        on = sess.generate(prompts, max_new=4)
    assert on == off
    names = [e["name"] for e in tr.events if e["name"].startswith("serve.")]
    assert names == ["serve.prefill", "serve.decode"] * 3    # 3 chunks
    assert tr.counters["serve.decode_steps"] == 3 * (4 - 1)


def test_pipeline_prefetch_and_cursor():
    shape = ShapeConfig("t", 16, 4, "train")
    p1 = DataPipeline(CFG, shape, seed=5, start_step=0, prefetch=2)
    batches = [next(p1) for _ in range(3)]
    p1.close()
    # resume from step 2 reproduces batch index 2
    p2 = DataPipeline(CFG, shape, seed=5, start_step=2, prefetch=0)
    b2 = next(p2)
    assert jnp.array_equal(batches[2]["tokens"], b2["tokens"])


def test_annealing_balancer():
    from repro.core.annealing import balance_assignment, buffer_depths
    rates = [5, 1, 1, 1, 1, 1]
    assign = balance_assignment(rates, 2, steps=300)
    loads = np.zeros(2)
    np.add.at(loads, assign, rates)
    assert abs(loads[0] - loads[1]) <= 1.01
    depths = buffer_depths([1.0, 2.0, 1.0])
    assert len(depths) == 3 and depths[1] >= depths[0]
