"""Unified model API: ``build_model(cfg)`` returns family-appropriate fns.

All families expose the same surface:
    init(rng) -> params
    loss(params, batch, **kw) -> (scalar, metrics)       [train step core]
    prefill(params, tokens, S_max, **kw) -> (logits, cache/state)
    decode_step(params, cache, token) -> (logits, new_cache)
    init_cache(B, S_max) -> cache pytree (zeros / ShapeDtypeStruct template)
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig


@dataclass
class ModelAPI:
    cfg: ModelConfig
    init: Callable
    loss: Callable
    prefill: Optional[Callable] = None
    decode_step: Optional[Callable] = None
    init_cache: Optional[Callable] = None


# serving-cache leaves that hold keys and values (or their latents); every
# other leaf but the positions is recurrent state
KV_CACHE_LEAVES = ("k", "v", "ckv", "krope", "xk", "xv")


def cache_bytes(cache: Dict[str, Any]) -> Dict[str, int]:
    """Bytes of a serving cache's KV entries and of its recurrent-state
    entries (an entry may hold a tuple of arrays, as Zamba2's state of
    each segment), from their shapes and dtypes (no device sync)."""
    out = {"kv": 0, "recurrent": 0}
    for name, leaf in cache.items():
        if name != "pos":
            kind = "kv" if name in KV_CACHE_LEAVES else "recurrent"
            out[kind] += sum(a.size * jnp.dtype(a.dtype).itemsize
                             for a in jax.tree_util.tree_leaves(leaf))
    return out


def build_model(cfg: ModelConfig) -> ModelAPI:
    if cfg.family == "cnn":
        from repro.models import cnn
        return ModelAPI(
            cfg=cfg,
            init=functools.partial(cnn.init_params, cfg),
            loss=functools.partial(cnn.loss, cfg))
    if cfg.rwkv is not None:
        from repro.models import rwkv
        return ModelAPI(
            cfg=cfg,
            init=functools.partial(rwkv.init_params, cfg),
            loss=functools.partial(rwkv.loss, cfg),
            prefill=functools.partial(rwkv.prefill, cfg),
            decode_step=functools.partial(rwkv.decode_step, cfg),
            init_cache=lambda B, S_max: rwkv.init_state(cfg, B))
    if cfg.ssm is not None:
        from repro.models import ssm
        return ModelAPI(
            cfg=cfg,
            init=functools.partial(ssm.init_params, cfg),
            loss=functools.partial(ssm.loss, cfg),
            prefill=functools.partial(ssm.prefill, cfg),
            decode_step=functools.partial(ssm.decode_step, cfg),
            init_cache=functools.partial(ssm.init_state, cfg))
    from repro.models import transformer as tfm
    return ModelAPI(
        cfg=cfg,
        init=functools.partial(tfm.init_params, cfg),
        loss=functools.partial(tfm.lm_loss, cfg),
        prefill=functools.partial(tfm.prefill, cfg),
        decode_step=functools.partial(tfm.decode_step, cfg),
        init_cache=functools.partial(tfm.init_cache, cfg))


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of a cell.

    train  -> {'tokens': (B, S)} (+frames for audio; images for cnn)
    prefill-> {'tokens': (B, S)} (+frames)
    decode -> {'token': (B, 1), 'cache': <pytree>}    (cache of size S)
    """
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32

    def sds(sh, dt=i32):
        return jax.ShapeDtypeStruct(sh, dt)

    if cfg.family == "cnn":
        return {"batch": {"images": sds((B, cfg.img_res, cfg.img_res, 3),
                                        jnp.bfloat16),
                          "labels": sds((B,))}}

    if shape.kind in ("train", "prefill"):
        batch = {"tokens": sds((B, S))}
        if cfg.is_encoder_decoder:
            batch["frames"] = sds((B, cfg.num_frames, cfg.d_model), jnp.bfloat16)
        return {"batch": batch}

    # decode: one new token against a populated cache of logical length S
    api = build_model(cfg)
    cache = jax.eval_shape(lambda: api.init_cache(B, S))
    return {"token": sds((B, 1)), "cache": cache}
