"""Bring-up smoke run of HASS on one TPU chip, at full width.

Drives the system's two jobs once through their normal entry points:

  kernel  the Pallas kernels compiled for the chip (``interpret=False``):
          ``SparseWeight.matmul`` on 50%-tile-pruned bf16 weights at the
          Qwen3-0.6B FFN widths and ``act_clip`` on a (256, d_ff)
          activation, both checked against ``kernels/ref.py``;
  serve   Qwen3-0.6B at published widths with seeded random weights:
          prefill + decode vs teacher-forced ``lm_forward`` in f32, then
          bf16 serving of seeded requests through ``ServeSession.generate``
          and ``serve_open_loop``;
  search  HASS on ResNet-18 at 224x224 with ``TPUModel``: the evaluator's
          prune+forward on the chip, serial vs vmapped agreement, then
          ``hass_search`` in vmapped waves.

    python chip_smoke.py

Exits non-zero, with no result line, when JAX finds no TPU. Every phase
prints its XLA compile seconds and its wall seconds, both on the host clock;
they are bring-up times, not benchmark metrics. The last line printed is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.paper_cnns import RESNET18  # noqa: E402
from repro.core import pruning  # noqa: E402
from repro.core.dse import engine_dispatch_stats  # noqa: E402
from repro.core.hass import CNNEvaluator, hass_search  # noqa: E402
from repro.core.perf_model import TPUModel  # noqa: E402
from repro.data.synthetic import image_batch  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.models import build_model, cnn  # noqa: E402
from repro.models import transformer as tfm  # noqa: E402
from repro.serve.serve_loop import ServeSession, requests_from_trace  # noqa: E402
from repro.sim.trace import poisson_trace  # noqa: E402

SEED = 0
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
F32_ACCUM_ULP = 2.0 ** -24         # unit roundoff of an f32 accumulator
DECODE_RTOL = 1e-4                 # as tests/test_decode_consistency.py
AGREE_RTOL, AGREE_ATOL = 1e-3, 1e-6   # as tests/test_hass_search.py


def compile_cache_dir() -> str:
    """Where the persistent compile cache lives: ``JAX_COMPILATION_CACHE_DIR``
    when set (jax reads it itself), else a fixed directory in the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def check(ok: bool, what: str) -> None:
    """A phase's correctness check; unlike ``assert`` it survives -O."""
    if not ok:
        raise AssertionError(what)


def require_tpu() -> jax.Device:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    return dev


# ---------------------------------------------------------------- phases
def kernel_phase(cfg, *, interpret: bool, m: int = 128,
                 seed: int = SEED) -> dict:
    """Block-sparse matmul at the config's FFN widths (up: d_model x d_ff,
    down: d_ff x d_model) on seeded weights with half their 128x128 tiles
    pruned, and the fused clip + zero count on a (256, d_ff) activation.

    The matmul feeds bf16 operands to an f32 accumulator, so every product
    is exact and only the K-term sums round: |out - ref| <= 2 K u (|x| @ |w|)
    elementwise, u = 2^-24, covering the kernel's and the reference's
    accumulation. The clip output and the zero count must match exactly.
    """
    rng = np.random.default_rng(seed)
    out = {}
    for name, (K, N) in (("up", (cfg.d_model, cfg.d_ff)),
                         ("down", (cfg.d_ff, cfg.d_model))):
        w, _ = pruning.tile_prune(
            jnp.asarray(rng.standard_normal((K, N)) / np.sqrt(K),
                        jnp.float32), 0.5)
        w = w.astype(jnp.bfloat16)
        x = jnp.asarray(rng.standard_normal((m, K)), jnp.bfloat16)
        sw = ops.SparseWeight(w)
        y = sw.matmul(x, interpret=interpret)
        xf, wf = x.astype(jnp.float32), w.astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            want = ref.block_sparse_matmul_ref(xf, wf, sw.mask, sw.bk, sw.bn)
            bound = 2 * K * F32_ACCUM_ULP * (jnp.abs(xf) @ jnp.abs(wf))
        err = jnp.abs(y - want)
        check(y.shape == (m, N) and y.dtype == jnp.float32,
              f"matmul {name}: got {y.shape} {y.dtype}")
        check(bool(jnp.all(err <= bound)),
              f"matmul {name}: max err {float(err.max())} over the bound")
        out[f"matmul_{name}"] = {"mkn": [m, K, N],
                                 "tile_density": sw.tile_density,
                                 "max_err": float(err.max()),
                                 "max_bound": float(bound.max())}

    a = jnp.asarray(rng.standard_normal((256, cfg.d_ff)), jnp.bfloat16)
    tau = 0.5                      # exact in bf16, so both compares agree
    y, cnt = ops.act_clip(a, tau, interpret=interpret)
    y_ref, cnt_ref = ref.act_clip_count_ref(a, tau)
    check(y.dtype == a.dtype and bool(jnp.array_equal(y, y_ref)),
          "act_clip output differs from the reference")
    check(int(cnt) == int(cnt_ref),
          f"act_clip zero count {int(cnt)} != reference {int(cnt_ref)}")
    out["act_clip"] = {"shape": list(a.shape), "zeros": int(cnt)}
    return out


class _CheckedSession(ServeSession):
    """A ``ServeSession`` that records whether every logits array it
    samples from (prefill and decode alike) is finite."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.finite = []

    def _sample(self, logits):
        self.finite.append(jnp.all(jnp.isfinite(logits)))
        return super()._sample(logits)


def serve_phase(cfg, *, n_requests: int = 16, n_open_loop: int = 6,
                prompt_len: int = 128, max_new: int = 32,
                batch_slots: int = 8, seed: int = SEED) -> dict:
    """Correctness in f32: prefill + decode steps reproduce teacher-forced
    ``lm_forward`` logits within ``DECODE_RTOL`` of their scale, under
    ``highest`` matmul precision (the TPU's default f32 matmul rounds its
    inputs to bf16). Then serving in the config's dtype: seeded requests
    through the closed-loop ``generate`` and the open-loop trace replay;
    every request gets ``max_new`` tokens and every logit is finite."""
    api = build_model(cfg)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    api32 = build_model(cfg32)
    params = jax.jit(api.init)(jax.random.PRNGKey(seed))   # f32 masters

    B, S, split = 2, 24, 16
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, S), 0,
                                cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        teacher = jax.jit(functools.partial(tfm.lm_forward, cfg32))(
            params, tokens)[1]
        last, cache = jax.jit(api32.prefill, static_argnums=2)(
            params, tokens[:, :split], S)
        errs = [float(jnp.abs(last[:, 0] - teacher[:, split - 1]).max())]
        decode = jax.jit(api32.decode_step)
        for t in range(split, S):
            lg, cache = decode(params, cache, tokens[:, t:t + 1])
            errs.append(float(jnp.abs(lg[:, 0] - teacher[:, t]).max()))
    scale = max(float(jnp.abs(teacher).max()), 1.0)
    check(max(errs) <= DECODE_RTOL * scale,
          f"decode vs teacher forcing: errors {errs}, scale {scale}")

    sess = _CheckedSession(api, params, batch_slots=batch_slots,
                           S_max=prompt_len + max_new)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=prompt_len)
               for _ in range(n_requests)]
    outs = sess.generate(prompts, max_new=max_new)
    check([len(o) for o in outs] == [max_new] * n_requests,
          f"generate: lengths {[len(o) for o in outs]}")

    # arrivals ~one request per 16 decode steps: groups overlap and the
    # batch composition changes from round to round
    trace = poisson_trace(n_open_loop, 1.0 / 16, sizes=max_new, seed=seed)
    reqs = requests_from_trace(trace, vocab_size=cfg.vocab_size,
                               prompt_len=prompt_len, seed=seed)
    rep = sess.serve_open_loop(reqs, step_cycles=1.0, prefill_cycles=4.0)
    check(rep.completed == n_open_loop and rep.shed == 0
          and all(len(r.out) == r.max_new for r in reqs),
          f"open loop: {rep.completed} completed, {rep.shed} shed")
    toks = np.concatenate([np.ravel(o) for o in outs] + [r.out for r in reqs])
    check(bool(((toks >= 0) & (toks < cfg.vocab_size)).all()),
          "token id out of the vocabulary")
    check(bool(jnp.all(jnp.stack(sess.finite))), "non-finite logits")
    return {"decode_max_err": max(errs), "logit_scale": scale,
            "generate": {"requests": n_requests,
                         "new_tokens": sum(map(len, outs))},
            "open_loop": {"requests": n_open_loop,
                          "decode_steps": rep.decode_steps,
                          "prefills": rep.prefills},
            "sampled_logit_arrays": len(sess.finite)}


def search_phase(cfg, *, n_images: int = 8, iters: int = 16,
                 batch_size: int = 8, seed: int = SEED) -> dict:
    """``CNNEvaluator`` on ``TPUModel`` (tile-structured pruning) over
    seeded synthetic images, then ``hass_search`` in vmapped waves of
    ``batch_size`` proposals. The serial evaluator and ``evaluate_batch``
    must agree on the same proposals; both run under ``highest`` matmul
    precision so that vmap-vs-jit reassociation, not bf16 input rounding,
    is the only difference (as in the f32 CPU test). All metrics finite."""
    hw = TPUModel()
    params = cnn.init_params(cfg, jax.random.PRNGKey(seed))
    images = image_batch(cfg, n_images, seed=seed)["images"]
    before = engine_dispatch_stats()
    with jax.default_matmul_precision("highest"):
        ev = CNNEvaluator(cfg, params, images, hw, budget=hw.chip_budget)
        L = len(ev.prunable)
        # one wave's worth of proposals: the vmapped program compiled here
        # is the one hass_search reuses
        xs = [np.full(2 * L, s) for s in np.linspace(0.0, 0.8, batch_size)]
        batch = ev.evaluate_batch(xs)
        # the dense proposal reproduces the dense reference's top-1
        check(batch[0]["acc"] == 1.0, f"dense proposal: {batch[0]}")
        for x, mb in zip(xs, batch):
            ms = ev(x)
            for k in ms:
                check(np.isclose(mb[k], ms[k], rtol=AGREE_RTOL,
                                 atol=AGREE_ATOL),
                      f"{k}: batched {mb[k]} vs serial {ms[k]}")
        res = hass_search(ev, L, iters=iters, s_max=0.9, seed=seed,
                          batch_size=batch_size)
    after = engine_dispatch_stats()
    check(len(res.trials) == iters, f"{len(res.trials)} trials")
    vals = [v for t in res.trials for v in t.metrics.values()]
    check(bool(np.all(np.isfinite(vals))), "non-finite search metrics")
    return {"img_res": cfg.img_res, "prunable_layers": L,
            "wave_sparsity": [float(x[0]) for x in xs],
            "wave_acc": [m["acc"] for m in batch],
            "trials": len(res.trials),
            "batch_shapes": sorted(ev.batch_shapes),
            "best": {k: float(v) for k, v in res.best_metrics.items()},
            "dse_engines": {k: after[k] - before[k] for k in after
                            if after[k] != before[k]}}


# ----------------------------------------------------------------- main
def _run_phase(name, fn, device_label):
    compiles = []

    def listen(event, duration, **_):
        if event == BACKEND_COMPILE:
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    t0 = time.perf_counter()
    try:
        result = fn()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    wall = time.perf_counter() - t0
    print(f"{name}: {json.dumps(result)}")
    print(f"phase {name} passed: compile {sum(compiles)} s in {len(compiles)} "
          f"XLA compiles, wall {wall} s (host clock, {device_label})",
          flush=True)
    return result


def main() -> None:
    cache = compile_cache_dir()
    print(f"compile cache: {cache}", flush=True)
    dev = require_tpu()
    count = len(jax.devices())
    label = f"{dev.platform} {dev.device_kind}"
    print(f"device: {label}, {count} device(s)", flush=True)

    lm = get_config("qwen3-0.6b")
    _run_phase("kernel", lambda: kernel_phase(lm, interpret=False), label)
    _run_phase("serve", lambda: serve_phase(lm), label)
    search = _run_phase("search", lambda: search_phase(RESNET18), label)
    print(f"dse engine runs: {json.dumps(search['dse_engines'])} (compiled = "
          "C kernel, lockstep = its numpy fallback, flat/grouped = serial)",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
