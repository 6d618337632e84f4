"""Shared model building blocks (pure JAX, no framework deps)."""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


# --------------------------------------------------------------------- #
# Initializers
# --------------------------------------------------------------------- #
def dense_init(key, shape, in_axis=-2, scale=1.0, dtype=jnp.float32):
    """LeCun-normal over the contracted axis; stored in float32, cast at use."""
    fan_in = shape[in_axis]
    std = scale / math.sqrt(fan_in)
    return (jax.random.normal(key, shape) * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# --------------------------------------------------------------------- #
# Normalization / activations
# --------------------------------------------------------------------- #
def rmsnorm(x, scale, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * scale).astype(dt)


def activation(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":                        # exact (erf), as published
        return functools.partial(jax.nn.gelu, approximate=False)
    if name == "relu2":                       # rwkv channel-mix
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(name)


# --------------------------------------------------------------------- #
# Rotary position embedding
# --------------------------------------------------------------------- #
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, D) with D even; positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                                # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]                         # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------- #
# Activation clipping — the paper's SPE "clip" unit (§IV).
# Values with |x| < tau are zeroed at run time (dynamic activation sparsity).
# --------------------------------------------------------------------- #
def act_clip(x, tau):
    """tau: scalar or per-layer scalar. tau<=0 disables (identity)."""
    if tau is None:
        return x
    return jnp.where(jnp.abs(x) >= tau, x, jnp.zeros_like(x))


def take_layer(stacked, i):
    """Slice layer i out of a stacked-parameter pytree."""
    return jax.tree_util.tree_map(lambda a: a[i], stacked)


# --------------------------------------------------------------------- #
# Scan wrapper with a global unroll switch.
#
# XLA's HloCostAnalysis counts a while-loop body ONCE regardless of trip
# count, so cost_analysis() on scanned programs under-reports FLOPs/bytes.
# The dry-run therefore uses an analytic cost model (analysis/flops_model.py)
# which tests validate against cost_analysis() of *unrolled* small configs —
# REPRO_UNROLL_SCANS=1 switches every model scan to a python loop.
# --------------------------------------------------------------------- #
import os as _os


def unroll_scans() -> bool:
    return _os.environ.get("REPRO_UNROLL_SCANS", "0") == "1"


def maybe_scan(body, carry, xs, length=None):
    """jax.lax.scan, or an unrolled python loop under REPRO_UNROLL_SCANS=1."""
    if not unroll_scans():
        return jax.lax.scan(body, carry, xs, length=length)
    n = length if length is not None else \
        jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        xi = None if xs is None else jax.tree_util.tree_map(lambda a: a[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    if ys and ys[0] is not None:
        stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)
    else:
        stacked = None
    return carry, stacked
