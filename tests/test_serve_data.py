"""Serving session + data pipeline."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduce_config
from repro.data.pipeline import DataPipeline
from repro.configs.base import ShapeConfig
from repro.models import build_model
from repro.serve.serve_loop import ServeSession

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
