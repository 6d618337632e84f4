"""Search cells: ``hass_search`` over one ``CNNEvaluator`` on ``TPUModel``.

A unit is one wave of the search (``batch_size`` proposals asked of the
TPE, pruned and run through the network in one vmapped call, scored by the
DSE and told back). Searches of ``iters`` trials run back to back, each
from a seed derived from ``--seed``; the window closes at the first unit
boundary after ``--seconds``.

``correct``: a sample of the window's trials, drawn from the seed, is
measured again by the plain reference (``reference/cnn.py`` for the
pruned forward, ``reference/dse.py`` for the hardware score) on weights
and images regenerated from the seed, and compared:

  median_layer_sparsity_gap
      per pruned layer, the largest |program - reference| over the sampled
      trials of the weight, weight-tile and activation sparsity that the
      evaluator's device program measured; the median over the layers.
      The median, as one small layer (the head's 32 x 512 input) alone
      swings with the forward's rounding from seed to seed;
  hw_metric_gap
      the largest gap of a sampled trial's reported thr, thr_norm, dsp and
      eff (the DSE's hardware terms), as |program - reference| /
      max(|reference|, 1).

The trial's ``spa`` is the weight-weighted mean of the sparsities compared
layer by layer, and is not compared on its own.
"""
from __future__ import annotations

import contextlib
import dataclasses
import sys

import jax
import numpy as np

from perfbench import costs, harness, weights
from perfbench.reference import cnn as ref_cnn
from perfbench.reference import dse as ref_dse

METRIC_KEYS = ("spa", "thr", "thr_norm", "dsp", "eff")
HW_KEYS = ("thr", "thr_norm", "dsp", "eff")


class WindowClosed(Exception):
    pass


class WindowCloser:
    """A ``hass_search`` recorder: keeps each told trial and ends the search
    at the first unit boundary at or after ``deadline``."""

    def __init__(self, deadline: float):
        self.deadline = deadline
        self.trials = []              # (x, metrics) in the order told
        self.t_close = None
        self._in_round = 0

    def header(self, *args, **kw):
        pass

    def footer(self, **kw):
        pass

    def trial(self, *, x, metrics, round_size, **_):
        self.trials.append((np.array(x, np.float64), dict(metrics)))
        self._in_round += 1
        if self._in_round == round_size:
            self._in_round = 0
            t = harness.now()
            if t >= self.deadline:
                self.t_close = t
                raise WindowClosed


class Tap:
    """Wraps one of the evaluator's compiled programs and keeps what each
    call returned, so that the check reads the timed path's own output."""

    def __init__(self, fn):
        self.fn, self.calls = fn, []

    def __call__(self, *args):
        out = self.fn(*args)
        self.calls.append(out)
        return out

    def rows(self):
        """(weight, activation, tile) sparsity rows, one per proposal."""
        sw, sa, swt = [], [], []
        for _, w, a, t in self.calls:
            w, a, t = (np.asarray(v, np.float64) for v in (w, a, t))
            sw.append(w.reshape(-1, w.shape[-1]))
            sa.append(a.reshape(-1, a.shape[-1]))
            swt.append(t.reshape(-1, t.shape[-1]))
        return (np.concatenate(sw), np.concatenate(sa), np.concatenate(swt))


def program_config(cfg: dict):
    """The registry's config at the file's sizes, checked layer by layer
    against the file's table."""
    from repro.configs import get_config
    from repro.models import cnn
    mcfg = dataclasses.replace(get_config(cfg["registry"]),
                               img_res=cfg["image_size"],
                               num_classes=cfg["num_classes"])
    specs = [s for s in cnn.build_specs(mcfg) if s.prunable]
    rows = costs.cnn_prunable(cfg)
    got = [(s.name, s.cin, s.cout, s.macs) for s in specs]
    want = [(l["name"], l["cin"], l["cout"], costs.cnn_layer_macs(l))
            for l in rows]
    if got != want:
        raise ValueError(f"program layers {got} differ from the "
                         f"configuration's {want}")
    return mcfg


def search_seed(seed: int, i: int) -> int:
    return int(np.random.SeedSequence([seed & (2 ** 63 - 1), 1, i])
               .generate_state(1)[0] & 0x7FFFFFFF)


def dse_layers(cfg: dict, tile_sparsity, low_precision: bool = False
               ) -> list:
    rows = costs.cnn_prunable(cfg)
    return [ref_dse.Layer(costs.cnn_layer_macs(l),
                          l["cin"] * l.get("k", 1) ** 2, s, low_precision)
            for l, s in zip(rows, tile_sparsity)]


def check(cfg: dict, traffic: dict, seed: int, trials, rows,
          low_precision: bool = False) -> dict:
    """The reference's readings on a seeded sample of ``trials``; with
    ``low_precision`` the control takes the program's place: the forward in
    float8, the DSE in float32."""
    n_imgs = traffic["images"]
    params, images = weights.cnn_params_and_images(
        cfg, weights.seed_key(seed), n_imgs)
    ref = ref_cnn.Reference(cfg, params, images)
    ctl = ref_cnn.Reference(cfg, params, images, low_precision=True) \
        if low_precision else None
    rows_cfg = costs.cnn_prunable(cfg)
    wc = np.array([l["cin"] * l["cout"] * l.get("k", 1) ** 2
                   for l in rows_cfg], np.float64)
    dense_hz = ref_dse.dense_rate_hz(dse_layers(cfg, [0.0] * len(wc)),
                                     traffic["dse_iters"])
    dense_hz_ctl = ref_dse.dense_rate_hz(
        dse_layers(cfg, [0.0] * len(wc), low_precision=True),
        traffic["dse_iters"]) if low_precision else None
    rng = np.random.default_rng([seed & (2 ** 63 - 1), 2])
    k = min(traffic["check_trials"], len(trials))
    pick = np.sort(rng.choice(len(trials), size=k, replace=False))
    d, hw = [], []
    for i in pick:
        x, m = trials[i]
        sw_r, swt_r, sa_r = ref.measure(x)
        if ctl is not None:             # the control in the program's place
            sw, swt, sa = ctl.measure(x)
            m = _metrics(cfg, traffic, sw, swt, sa, wc, dense_hz_ctl,
                         low_precision=True)
        else:
            sw, sa, swt = (r[i] for r in rows)
        r = _metrics(cfg, traffic, sw_r, swt_r, sa_r, wc, dense_hz)
        d.append(np.abs(np.stack([sw - sw_r, swt - swt_r, sa - sa_r])))
        hw.extend(abs(m[k] - r[k]) / max(abs(r[k]), 1.0) for k in HW_KEYS)
    per_layer = np.array(d).max(axis=(0, 1))    # worst trial and kind
    return {"median_layer_sparsity_gap": float(np.median(per_layer)),
            "hw_metric_gap": float(max(hw))}


def _metrics(cfg, traffic, sw, swt, sa, wc, dense_hz,
             low_precision: bool = False) -> dict:
    """The trial's reported terms from measured sparsities: weight-count
    weighted mean of (s_w + s_a) / 2, and the hardware terms of the DSE."""
    spa = 0.0
    for w, a, c in zip(sw, sa, wc):
        spa += (float(w) + float(a)) / 2 * c
    out = ref_dse.hardware_terms(dse_layers(cfg, swt, low_precision),
                                 dense_hz, traffic["dse_iters"])
    out["spa"] = spa / float(np.sum(wc))
    return out


def run(run: harness.Run) -> harness.Outcome:
    from repro.core.hass import CNNEvaluator, hass_search
    from repro.core.perf_model import TPUModel
    from repro.obs import Tracer, use_tracer

    cfg, traffic = run.cell.config, run.cell.traffic
    mcfg = program_config(cfg)
    params, images = weights.cnn_params_and_images(
        cfg, weights.seed_key(run.seed), traffic["images"])
    hw = TPUModel()
    ev = CNNEvaluator(mcfg, params, images, hw, budget=hw.chip_budget,
                      dse_iters=traffic["dse_iters"])
    L = len(ev.prunable)
    batch = traffic["batch_size"]

    # warm-up: one unit of the cell's own shape, on proposals of its own
    warm = np.random.default_rng([run.seed & (2 ** 63 - 1), 3]).uniform(
        0.0, traffic["s_max"], (batch, 2 * L))
    ev._eval_batch = tap = Tap(ev._eval_batch)
    ev.evaluate_batch(list(warm))
    jax.block_until_ready(tap.calls[-1])
    tap.calls.clear()

    tracer = Tracer() if run.trace else None
    prof = harness.Profiler(run.trace)
    with harness.CompileCounter() as compiles, prof, \
            use_tracer(tracer) if tracer else contextlib.nullcontext():
        with prof.annotate("perfbench.window"):
            t_start = harness.now()
            closer = WindowCloser(t_start + run.seconds)
            i = 0
            try:
                while True:
                    hass_search(ev, L, iters=traffic["iters"],
                                s_max=traffic["s_max"],
                                seed=search_seed(run.seed, i),
                                batch_size=batch, liar=traffic["liar"],
                                recorder=closer)
                    i += 1
            except WindowClosed:
                pass
    window_s = closer.t_close - t_start
    peak = harness.memory_peak_bytes(run.devices)
    spans = [(e["name"], e["t0"], e["t1"])
             for e in (tracer.events if tracer else [])]
    trace = prof.reduce("perfbench.window", host_spans=spans,
                        window_start_s=t_start)
    trials = closer.trials
    rows = tap.rows()
    # every told trial must have come out of the device program once
    aligned = len(rows[0]) == len(trials)
    failed = sum(not all(np.isfinite(m[k]) for k in METRIC_KEYS)
                 for _, m in trials) if aligned else len(trials)
    span_s = {}
    for name, t0, t1 in spans:
        span_s[name] = span_s.get(name, 0.0) + t1 - t0
    record = {
        "setup_s": t_start - run.t_process, "window_s": window_s,
        "trials": len(trials), "searches": i + 1,
        "kept_flops": sum(costs.cnn_kept_flops(cfg, x[:L],
                                               traffic["images"])
                          for x, _ in trials),
        "span_s": span_s, "trace": trace, "compiles_in_window": compiles.n,
        "device_kind": run.devices[0].device_kind,
    }
    del ev, params, images, tap
    if not aligned:
        print(f"search: {len(rows[0])} device rows for {len(trials)} "
              "trials", file=sys.stderr)
        values = {k: float("nan") for k in run.cell.checks}
    else:
        values = check(cfg, traffic, run.seed, trials, rows)
    control = check(cfg, traffic, run.seed, trials, rows,
                    low_precision=True) if run.control and aligned else None
    return harness.Outcome(attempted=len(trials), failed=failed,
                           record=record,
                           checks=harness.check_limits(run.cell, values),
                           memory_peak_bytes=peak, trace=trace,
                           values=values, control=control)
