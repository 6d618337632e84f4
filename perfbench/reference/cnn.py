"""Plain reference of one search trial on a CNN: tile pruning, the
calibrated activation clipping, and the forward pass that measures how
sparse each layer's input is.

Written from the configuration's layer table and the HASS pruning rules
(arXiv 2406.03088, Section IV, with the TPU's 128x128 weight tiles):

* weights: a conv's (k, k, cin, cout) weight is the (k*k*cin, cout) matrix
  its matmul runs; zero-padded to whole 128x128 tiles, the tiles whose mean
  |w| lies below the requested quantile of all tiles' means are zeroed;
* activations: a calibration pass of the dense network takes 256 quantiles
  (levels 0 to 0.999) of |x| at each pruned layer's input; a proposal's
  activation sparsity s picks quantile int(256 s) as the clip threshold,
  and values with |x| below it are zeroed before the layer;
* measured: per pruned layer, the fraction of zero weights, of all-zero
  weight tiles, and of zero inputs after clipping.

Float32 throughout at ``highest`` matmul precision, or, for the control,
with every conv and linear operand rounded to float8 (e4m3, one absmax
scale per tensor). Imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
N_QUANTILES = 256
TILE = 128


def fp8_round(a):
    """``a`` rounded to float8 e4m3 under one absmax scale, back in f32."""
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def prunable(cfg):
    return [l for l in cfg["layers"] if l["kind"] in ("conv", "linear")]


def tile_prune(w, s):
    """Zero the lowest-mean-|w| 128x128 tiles of ``w`` seen as a matrix.
    Returns (pruned w, fraction of zero weights, fraction of zero tiles)."""
    shape = w.shape
    m = w.reshape(-1, shape[-1])
    K, N = m.shape
    Kp, Np = -(-K // TILE) * TILE, -(-N // TILE) * TILE
    padded = jnp.zeros((Kp, Np), m.dtype).at[:K, :N].set(m)
    tiles = padded.reshape(Kp // TILE, TILE, Np // TILE, TILE)
    means = jnp.abs(tiles).mean(axis=(1, 3))
    cut = jnp.quantile(means.ravel(), jnp.clip(s, 0.0, 1.0))
    keep = (means >= cut) | (s <= 0.0)
    kept = tiles * keep[:, None, :, None]
    zero_tiles = 1.0 - jnp.mean(jnp.any(kept != 0, axis=(1, 3)))
    out = kept.reshape(Kp, Np)[:K, :N].reshape(shape)
    return out, jnp.mean(out == 0.0), zero_tiles


def forward(cfg, params, images, clips=None, low_precision=False):
    """Runs the layer table. ``clips`` maps a pruned layer to its threshold;
    returns (logits, {pruned layer: its input}, {pruned layer: zero
    fraction of its clipped input})."""
    q = fp8_round if low_precision else (lambda a: a)
    outs = {"input": images}
    inputs, zeros = {}, {}
    last = "input"
    for l in cfg["layers"]:
        x = outs[l.get("input", last)]
        kind = l["kind"]
        if kind in ("conv", "linear"):
            inputs[l["name"]] = x
            if clips is not None:
                x = jnp.where(jnp.abs(x) >= clips[l["name"]], x, 0.0)
                zeros[l["name"]] = jnp.mean(x == 0.0)
            p = params[l["name"]]
            if kind == "conv":
                pad = (l["k"] - 1) // 2
                x = jax.lax.conv_general_dilated(
                    q(x), q(p["w"]), (l["stride"], l["stride"]),
                    [(pad, pad), (pad, pad)],
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    precision=HIGHEST)
            else:
                x = jnp.dot(q(x), q(p["w"]), precision=HIGHEST)
            x = x + p["b"]
        elif kind == "pool":
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, l["k"], l["k"], 1),
                                      (1, l["stride"], l["stride"], 1),
                                      "SAME")
        elif kind == "gap":
            x = x.mean(axis=(1, 2))
        elif kind == "add":
            x = x + outs[l["residual"]]
        else:
            raise ValueError(f"layer kind {kind!r}")
        if l.get("act") == "relu":
            x = jnp.maximum(x, 0.0)
        elif l.get("act") not in (None, "none"):
            raise ValueError(f"activation {l['act']!r}")
        outs[l["name"]] = x
        last = l["name"]
    return outs[last], inputs, zeros


class Reference:
    """Calibrates once on the dense network, then measures proposals."""

    def __init__(self, cfg, params, images, low_precision=False):
        self.cfg, self.params, self.images = cfg, params, images
        self.low = low_precision
        self.rows = prunable(cfg)
        levels = jnp.linspace(0.0, 0.999, N_QUANTILES)

        @jax.jit
        def calibrate(params, images):
            _, inputs, _ = forward(cfg, params, images,
                                   low_precision=low_precision)
            return jnp.stack([jnp.quantile(jnp.abs(inputs[l["name"]]).ravel(),
                                           levels) for l in self.rows])

        @jax.jit
        def trial(params, images, table, s_w, s_a):
            pruned = dict(params)
            sw, swt, clips = [], [], {}
            for i, l in enumerate(self.rows):
                w, z, zt = tile_prune(params[l["name"]]["w"], s_w[i])
                pruned[l["name"]] = dict(params[l["name"]], w=w)
                sw.append(z)
                swt.append(zt)
                idx = jnp.clip((s_a[i] * N_QUANTILES).astype(jnp.int32),
                               0, N_QUANTILES - 1)
                clips[l["name"]] = table[i, idx]
            _, _, zeros = forward(cfg, pruned, images, clips,
                                  low_precision=low_precision)
            sa = [zeros[l["name"]] for l in self.rows]
            return jnp.stack(sw), jnp.stack(swt), jnp.stack(sa)

        self._trial = trial
        self.table = calibrate(params, images)

    def measure(self, x):
        """Per-layer (weight, tile, activation) sparsity of proposal ``x``
        (``[s_w per layer, s_a per layer]``, as float32 like the search's
        device arrays)."""
        n = len(self.rows)
        x = np.asarray(x, np.float32)
        out = self._trial(self.params, self.images, self.table,
                          jnp.asarray(x[:n]), jnp.asarray(x[n:2 * n]))
        return tuple(np.asarray(a, np.float64) for a in out)
