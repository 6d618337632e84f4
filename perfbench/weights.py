"""Seeded weights and inputs, made on the device in one jitted call.

The same seed always gives the same arrays, on the chip and in the
reference, which regenerates them from the seed rather than taking them
from the program. The pytrees follow the layouts the program's entry
points take (``repro.models.cnn`` and ``repro.models.transformer``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, *salt: int) -> jax.Array:
    """A PRNG key from a seed of any size, 64-bit seeds included."""
    words = np.random.SeedSequence([int(seed) & (2 ** 63 - 1), *salt]) \
        .generate_state(2)
    key = jax.random.PRNGKey(int(words[0]) & 0x7FFFFFFF)
    return jax.random.fold_in(key, int(words[1]) & 0x7FFFFFFF)


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def cnn_params_and_images(cfg: dict, key, n_images: int):
    """Conv weights (k, k, cin, cout) and linear weights (cin, cout) at
    LeCun scale, small random biases, and ``n_images`` standard-normal
    images of the configured size, all float32."""
    convs = [l for l in cfg["layers"] if l["kind"] in ("conv", "linear")]
    res = cfg["image_size"]

    def make(key):
        keys = jax.random.split(key, 2 * len(convs) + 1)
        params = {}
        for i, l in enumerate(convs):
            if l["kind"] == "conv":
                shape = (l["k"], l["k"], l["cin"], l["cout"])
                fan_in = l["k"] ** 2 * l["cin"]
            else:
                shape, fan_in = (l["cin"], l["cout"]), l["cin"]
            params[l["name"]] = {
                "w": _normal(keys[2 * i], shape, fan_in ** -0.5),
                "b": _normal(keys[2 * i + 1], (l["cout"],), 0.01)}
        images = _normal(keys[-1], (n_images, res, res, 3), 1.0)
        return params, images

    return jax.jit(make)(key)


def lm_params(cfg: dict, key):
    """A Qwen3-style decoder's float32 master weights: LeCun-scaled
    projections, norm scales near 1, embedding at 0.02. The LM head is
    tied to the embedding; where ``tie_word_embeddings`` is false it is a
    (hidden, vocab) ``lm_head`` leaf of its own, from a key the tied
    weights do not use, so that they stay the same."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    L, H = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    KV, hd, V = cfg["num_key_value_heads"], cfg["head_dim"], cfg["vocab_size"]

    def make(key):
        k = iter(jax.random.split(key, 16))

        def norm(shape):
            return 1.0 + _normal(next(k), shape, 0.1)
        params = {
            "embed": _normal(next(k), (V, d), 0.02),
            "final_norm": norm((d,)),
            "blocks": {
                "ln1": norm((L, d)),
                "ln2": norm((L, d)),
                "attn": {
                    "wq": _normal(next(k), (L, d, H * hd), d ** -0.5),
                    "wk": _normal(next(k), (L, d, KV * hd), d ** -0.5),
                    "wv": _normal(next(k), (L, d, KV * hd), d ** -0.5),
                    "wo": _normal(next(k), (L, H * hd, d), (H * hd) ** -0.5),
                    "q_norm": norm((L, hd)),
                    "k_norm": norm((L, hd)),
                },
                "ffn": {
                    "w_gate": _normal(next(k), (L, d, f), d ** -0.5),
                    "w_up": _normal(next(k), (L, d, f), d ** -0.5),
                    "w_down": _normal(next(k), (L, f, d), f ** -0.5),
                },
            },
        }
        if not cfg["tie_word_embeddings"]:
            params["lm_head"] = _normal(next(k), (d, V), d ** -0.5)
        return params

    return jax.jit(make)(key)
