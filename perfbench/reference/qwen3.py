"""Plain reference of a Qwen3 dense decoder's logits.

Written from the published architecture (Qwen3 technical report and the
model card's config.json): token embedding; per block a pre-norm RMSNorm,
grouped-query attention whose q and k heads get their own RMSNorm and
rotary positions (rotate-half, base ``rope_theta``) and causal softmax at
1/sqrt(head_dim), a residual add, a second RMSNorm, a SwiGLU feed-forward
(silu(x Wg) * (x Wu)) Wd and a residual add; a final RMSNorm and the LM
head: the embedding's transpose where ``tie_word_embeddings`` holds, else
a (hidden, vocab) ``lm_head`` leaf of its own. The norm epsilon is the
configuration file's.

Float32 at ``highest`` matmul precision over the whole sequence at once,
with no cache. For the control every matmul operand is rounded to float8
(e4m3): weights under one absmax scale per tensor, activations under one
per row. Imports nothing of the program.

``params(cfg, key)`` gives the seeded float32 masters in the layout the
program's entry points take (``weights.lm_params``, with an ``lm_head``
leaf where the head is untied); the serve job hands the same weights to
the program and to this reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.weights import lm_params as params  # noqa: F401

HIGHEST = jax.lax.Precision.HIGHEST


def _fp8(a, per_row):
    axes = -1 if per_row else None
    scale = jnp.maximum(jnp.max(jnp.abs(a), axis=axes, keepdims=per_row),
                        1e-30) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (S, heads, hd); position of row s is s."""
    S, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def make_logits(cfg: dict, low_precision: bool = False):
    """Returns a jitted ``logits(params, tokens (S,), first, count)`` that
    gives the (count, vocab) logits predicting positions ``first + 1`` to
    ``first + count`` (static ``count``)."""
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps, theta = cfg["head_dim"], cfg["rms_norm_eps"], cfg["rope_theta"]
    tied = bool(cfg["tie_word_embeddings"])
    G = H // KV

    if low_precision:
        def mm(x, w):
            return jnp.matmul(_fp8(x, True), _fp8(w, False),
                              precision=HIGHEST)

        def act(a):
            return _fp8(a, True)
    else:
        def mm(x, w):
            return jnp.matmul(x, w, precision=HIGHEST)

        def act(a):
            return a

    def block(h, p):
        S = h.shape[0]
        a = p["attn"]
        x = _rms(h, p["ln1"], eps)
        q = _rms(mm(x, a["wq"]).reshape(S, H, hd), a["q_norm"], eps)
        k = _rms(mm(x, a["wk"]).reshape(S, KV, hd), a["k_norm"], eps)
        v = mm(x, a["wv"]).reshape(S, KV, hd)
        q = _rope(q, theta).reshape(S, KV, G, hd)
        k = _rope(k, theta)
        s = jnp.einsum("skgd,tkd->kgst", act(q), act(k),
                       precision=HIGHEST) / jnp.sqrt(jnp.float32(hd))
        causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(causal, s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        vt = jnp.swapaxes(v, 0, 2)                        # (hd, KV, S)
        o = jnp.einsum("kgst,dkt->skgd", act(pr), act(vt), precision=HIGHEST)
        h = h + mm(o.reshape(S, H * hd), a["wo"])
        x = _rms(h, p["ln2"], eps)
        f = p["ffn"]
        g = jax.nn.silu(mm(x, f["w_gate"])) * mm(x, f["w_up"])
        return h + mm(g, f["w_down"]), None

    @functools.partial(jax.jit, static_argnums=(3,))
    def logits(params, tokens, first, count):
        h = params["embed"][tokens]
        h, _ = jax.lax.scan(block, h, params["blocks"])
        h = jax.lax.dynamic_slice_in_dim(h, first, count, axis=0)
        h = _rms(h, params["final_norm"], eps)
        return mm(h, params["embed"].T if tied else params["lm_head"])

    return logits
