"""Serve cells: ``ServeSession.generate`` in a closed loop.

A unit is one ``generate`` call: ``batch_slots`` requests of
``prompt_len`` prompt tokens each, greedy, ``max_new`` new tokens each.
Every call gets fresh token ids drawn from the seed; all calls have the
same sizes. The window closes at the first call boundary after
``--seconds``.

What belongs to one architecture comes from the configuration's
``family``, two files found by name:

  families/<family>.py   ``program_config(cfg)``, the registry config that
                         serves it, and ``request_flops(cfg, prompt_len,
                         max_new)``, the record's ``flops``
  reference/<family>.py  ``params(cfg, key)``, the seeded float32 masters
                         in the program's layout, and ``make_logits(cfg,
                         low_precision=False)``, the plain reference
                         (float32 at ``highest``) and its control

``correct``: once the window has closed, a sample of the finished requests
(``check_requests`` of them, one from each equal block of the slots, from
calls drawn from the seed) is run through the family's plain reference,
on weights regenerated from the seed, prompt and served tokens together:

  served_logit_gap  the widest gap, over every sampled served token, by
                    which the reference's logit of the served token lies
                    below the reference's best logit at that position.

The served tokens come from the prefill (the first) and from the decode
steps (the rest), so the gap covers both.

Under ``--trace 1`` a ``repro.obs`` tracer is installed around the window:
the record's ``span_s`` holds the seconds of each program span and
``counters`` the tracer's counters at the window's end.
"""
from __future__ import annotations

import contextlib
import gc

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import harness, weights

WARM_CALL = 2 ** 32       # the warm-up's ids: a call index never timed


def prompts(cfg: dict, traffic: dict, seed: int, call: int) -> np.ndarray:
    """The (batch_slots, prompt_len) token ids of call ``call``."""
    rng = np.random.default_rng([seed & (2 ** 63 - 1), 4, call])
    return rng.integers(0, cfg["vocab_size"],
                        (traffic["batch_slots"], traffic["prompt_len"]),
                        dtype=np.int32)


def check_sample(seed: int, n_calls: int, slots: int, n: int) -> list:
    """The (call, slot) pairs ``correct`` compares: one request from each
    of ``n`` equal blocks of the batch's slots, each from a call drawn from
    the seed, so that every part of the batch is read."""
    rng = np.random.default_rng([seed & (2 ** 63 - 1), 5])
    out = []
    for j in range(n):
        lo = j * slots // n
        hi = max((j + 1) * slots // n, lo + 1)
        out.append((int(rng.integers(n_calls)), int(rng.integers(lo, hi))))
    return out


def gaps(cfg: dict, reference, seed: int, prompt_rows, served_rows,
         low_precision: bool = False) -> np.ndarray:
    """Per request, the widest gap between the ``reference`` module's best
    logit and its logit of the served token. With ``low_precision`` the
    served token at each position is the one the reference a precision
    below puts first (the control: read on the same prompts and tokens,
    not decoded)."""
    params = reference.params(cfg, weights.seed_key(seed))
    ref = reference.make_logits(cfg)
    ctl = reference.make_logits(cfg, low_precision=True) \
        if low_precision else None
    out = []
    for p, y in zip(prompt_rows, served_rows):
        toks = jnp.asarray(np.concatenate([p, y[:-1]]).astype(np.int32))
        first, n = len(p) - 1, len(y)
        lg = ref(params, toks, first, n)
        if ctl is not None:
            y = np.asarray(jnp.argmax(ctl(params, toks, first, n), axis=-1))
        best = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(lg, jnp.asarray(y)[:, None], axis=-1)[:, 0]
        out.append(float(jnp.max(best - got)))
    return np.array(out)


def run(run: harness.Run) -> harness.Outcome:
    from repro.models import build_model
    from repro.obs import Tracer, use_tracer
    from repro.serve.serve_loop import ServeSession

    cfg, traffic = run.cell.config, run.cell.traffic
    family = harness.load_module("families", cfg["family"], run.cell.root)
    reference = harness.load_module("reference", cfg["family"],
                                    run.cell.root)
    B, P, N = traffic["batch_slots"], traffic["prompt_len"], \
        traffic["max_new"]
    api = build_model(family.program_config(cfg))
    params = reference.params(cfg, weights.seed_key(run.seed))
    sess = ServeSession(api, params, batch_slots=B, S_max=P + N)

    # warm-up: one call of the cell's own shape on ids of its own
    sess.generate(list(prompts(cfg, traffic, run.seed, WARM_CALL)), max_new=N)

    tracer = Tracer() if run.trace else None
    prof = harness.Profiler(run.trace)
    calls = []                      # (start, end, prompts, outputs)
    with harness.CompileCounter() as compiles, prof, \
            use_tracer(tracer) if tracer else contextlib.nullcontext():
        with prof.annotate("perfbench.window"):
            t_start = harness.now()
            while True:
                ids = prompts(cfg, traffic, run.seed, len(calls))
                with prof.annotate("perfbench.generate"):
                    c0 = harness.now()
                    outs = sess.generate(list(ids), max_new=N)
                    c1 = harness.now()
                calls.append((c0, c1, ids, np.array(outs, np.int64)))
                if c1 - t_start >= run.seconds:
                    break
    window_s = calls[-1][1] - t_start
    peak = harness.memory_peak_bytes(run.devices)
    trace = prof.reduce("perfbench.window", window_start_s=t_start)
    V = cfg["vocab_size"]
    failed = sum(int(o.shape != (N,) or o.min() < 0 or o.max() >= V)
                 for *_, outs in calls for o in outs)
    record = {
        "setup_s": t_start - run.t_process, "window_s": window_s,
        "calls": len(calls), "requests": B * len(calls),
        "output_tokens": B * N * len(calls),
        "flops": len(calls) * B * family.request_flops(cfg, P, N),
        "batch_slots": B, "prompt_len": P, "max_new": N, "config": cfg,
        "span_s": harness.span_seconds(tracer),
        "counters": dict(tracer.counters) if tracer else {},
        "trace": trace, "compiles_in_window": compiles.n,
        "device_kind": run.devices[0].device_kind,
    }

    # the reference runs once the program's state is gone
    del sess, params, api
    gc.collect()
    jax.clear_caches()
    sel = check_sample(run.seed, len(calls), B, traffic["check_requests"])
    rows = ([calls[c][2][r] for c, r in sel], [calls[c][3][r] for c, r in sel])
    values = {"served_logit_gap":
              float(gaps(cfg, reference, run.seed, *rows).max())}
    control = {"served_logit_gap":
               float(gaps(cfg, reference, run.seed, *rows,
                          low_precision=True).max())} \
        if run.control else None
    return harness.Outcome(attempted=B * len(calls), failed=failed,
                           record=record,
                           checks=harness.check_limits(run.cell, values),
                           memory_peak_bytes=peak, trace=trace,
                           values=values, control=control)
