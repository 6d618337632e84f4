"""Mamba2 (SSD) blocks and the Zamba2 hybrid (Mamba2 + shared attention).

Mamba2 recurrence per head h (state in R^{hd x N}):
    a_t = exp(-dt_t * exp(A_log))            (scalar per head)
    H_t = a_t * H_{t-1} + (dt_t * x_t) ⊗ B_t
    y_t = H_t · C_t + D ⊙ x_t
with a depthwise causal conv (width 4) in front of x/B/C and a silu(z) gate.

Zamba2 applies one *shared* (weight-tied) full-attention transformer block
every ``hybrid_attn_every`` mamba layers; its input is proj(concat(h, h_emb0))
per the Zamba recipe (per-invocation LoRA omitted — noted in DESIGN.md).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.ctx import shard
from repro.models import transformer as tfm
from repro.models.common import (act_clip, dense_init, dtype_of, embed_init,
                                 maybe_scan, rmsnorm)

Params = Dict[str, Any]


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    H = d_in // s.head_dim
    return d_in, H, s.head_dim, s.state_dim, s.conv_dim


def init_mamba_params(cfg: ModelConfig, rng, L: int) -> Params:
    d = cfg.d_model
    d_in, H, hd, N, K = _dims(cfg)
    conv_ch = d_in + 2 * N
    ks = jax.random.split(rng, 8)
    return {
        "ln": jnp.ones((L, d)),
        "in_proj": dense_init(ks[0], (L, d, 2 * d_in + 2 * N + H)),
        "conv_w": dense_init(ks[1], (L, K, conv_ch), in_axis=-2),
        "conv_b": jnp.zeros((L, conv_ch)),
        "A_log": jnp.zeros((L, H)),
        "D": jnp.ones((L, H)),
        "dt_bias": jnp.zeros((L, H)),
        "out_norm": jnp.ones((L, d_in)),
        "out_proj": dense_init(ks[2], (L, d_in, d)),
    }


def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv. x:(B,S,C), w:(K,C). state:(B,K-1,C) or None."""
    K = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)                   # (B,S+K-1,C)
    out = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(K)) + b
    return jax.nn.silu(out), xp[:, -(K - 1):]                # new conv state


def mamba_block(p, x, cfg: ModelConfig, state=None, act_tau=None):
    """x: (B,S,d). state: {'conv': (B,K-1,C), 'ssm': (B,H,hd,N)} or None."""
    B, S, d = x.shape
    d_in, H, hd, N, K = _dims(cfg)
    x = act_clip(x, act_tau)
    zxbcdt = x @ p["in_proj"]
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * N], axis=-1)
    conv_state = state["conv"] if state else None
    xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"], conv_state)
    xs, Bc, Cc = jnp.split(xbc, [d_in, d_in + N], axis=-1)
    xs = xs.reshape(B, S, H, hd)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])     # (B,S,H)
    a = jnp.exp(-dt * jnp.exp(p["A_log"]))                          # (B,S,H)
    dx = (dt[..., None] * xs.astype(jnp.float32))                   # (B,S,H,hd)

    def step(Hst, inp):
        a_t, dx_t, B_t, C_t = inp           # (B,H) (B,H,hd) (B,N) (B,N)
        Hst = a_t[..., None, None] * Hst + \
            jnp.einsum("bhd,bn->bhdn", dx_t, B_t.astype(jnp.float32))
        y = jnp.einsum("bhdn,bn->bhd", Hst, C_t.astype(jnp.float32))
        return Hst, y

    H0 = state["ssm"] if state else jnp.zeros((B, H, hd, N), jnp.float32)
    xs_t = tuple(jnp.moveaxis(v, 1, 0) for v in (a, dx, Bc, Cc))
    H_new, ys = maybe_scan(step, H0, xs_t)
    y = jnp.moveaxis(ys, 0, 1)                                      # (B,S,H,hd)
    y = y + p["D"][:, None] * xs.astype(jnp.float32)
    y = y.reshape(B, S, d_in).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z), p["out_norm"], cfg.norm_eps)
    y = shard(y, "batch", None, "ff")
    out = y @ p["out_proj"]
    new_state = {"conv": new_conv, "ssm": H_new}
    return out, new_state


# --------------------------------------------------------------------- #
# Zamba2 hybrid model
# --------------------------------------------------------------------- #
def _n_shared(cfg: ModelConfig) -> int:
    return -(-cfg.num_layers // cfg.hybrid_attn_every)      # ceil


def init_params(cfg: ModelConfig, rng) -> Params:
    ks = jax.random.split(rng, 6)
    p: Params = {
        "embed": embed_init(ks[0], (cfg.vocab_size, cfg.d_model)),
        "mamba": init_mamba_params(cfg, ks[1], cfg.num_layers),
        "final_norm": jnp.ones((cfg.d_model,)),
    }
    if cfg.hybrid_attn_every:
        p["shared"] = tfm._block_params(ks[2], cfg, 1)      # one weight-tied block
        p["shared_proj"] = dense_init(ks[3], (2 * cfg.d_model, cfg.d_model))
    if not cfg.tied_embeddings:
        p["lm_head"] = dense_init(ks[4], (cfg.d_model, cfg.vocab_size))
    return p


def _shared_attn(cfg, params, h, h0, positions, cache=None, pos=None,
                 window=0, return_kv_eff=0, site=0):
    """Apply the weight-tied attention block. cache: the KV of every
    invocation site, (n_sites, B, S, KV, hd); decode writes site ``site``'s
    rows into it and returns it whole.
    return_kv_eff>0 (train path): also return the last ``eff`` K/V rows,
    right-padded — the prefill cache for this invocation site."""
    dt = h.dtype
    p = tfm._cast(jax.tree_util.tree_map(lambda a: a[0], params["shared"]), dt)
    z = jnp.concatenate([h, h0], axis=-1) @ params["shared_proj"].astype(dt)
    x = rmsnorm(z, p["ln1"], cfg.norm_eps)
    if cache is None:
        o = tfm.attention_block(p["attn"], x, cfg, positions, causal=True)
        new_cache = None
        if return_kv_eff:
            q, kk, vv = tfm._gqa_qkv(p["attn"], x, cfg, positions)

            def to_cache(a):
                eff = return_kv_eff
                if a.shape[1] >= eff:
                    return a[:, -eff:]
                pad = [(0, 0)] * a.ndim
                pad[1] = (0, eff - a.shape[1])
                return jnp.pad(a, pad)
            new_cache = {"k": to_cache(kk), "v": to_cache(vv)}
    else:
        o, new_cache = tfm._gqa_decode_attn(p["attn"], x, cfg, cache, site,
                                            pos, window)
    x2 = rmsnorm(z + o, p["ln2"], cfg.norm_eps)
    y, _ = tfm.ffn_block(p["ffn"], x2, cfg)
    return h + z + o + y, new_cache


def forward(cfg: ModelConfig, params, tokens, *, sparsity=None, remat=None,
            state=None, return_state=False, S_max: int = 0):
    dt = dtype_of(cfg.dtype)
    B, S = tokens.shape
    h = params["embed"].astype(dt)[tokens]
    h = shard(h, "batch", None, "embed")
    h0 = h
    positions = jnp.arange(S)
    k = cfg.hybrid_attn_every
    L = cfg.num_layers
    d_in, Hh, hd, N, K = _dims(cfg)

    def mamba_step(h, xs):
        p, taus = xs
        p = tfm._cast(p, dt)
        f_tau = taus.get("ffn") if taus else None
        x = rmsnorm(h, p["ln"], cfg.norm_eps)
        if return_state:
            zero = {"conv": jnp.zeros((B, K - 1, d_in + 2 * N), dt),
                    "ssm": jnp.zeros((B, Hh, hd, N), jnp.float32)}
            y, st = mamba_block(p, x, cfg, state=zero, act_tau=f_tau)
            return h + y, st
        y, _ = mamba_block(p, x, cfg, act_tau=f_tau)
        return h + y, 0.0

    if remat:
        mamba_step = jax.checkpoint(mamba_step)

    groups = [(g * k, min((g + 1) * k, L)) for g in range(_n_shared(cfg))] \
        if k else [(0, L)]
    states, attn_kv = [], []
    eff = min(S_max or S, 4096)
    for (lo, hi) in groups:
        if k:
            h, kv = _shared_attn(cfg, params, h, h0, positions,
                                 return_kv_eff=eff if return_state else 0)
            if return_state:
                attn_kv.append(kv)
        sub = jax.tree_util.tree_map(lambda a: a[lo:hi], params["mamba"])
        taus = jax.tree_util.tree_map(lambda a: a[lo:hi], sparsity) \
            if sparsity else None
        if taus is None:
            h, ys = maybe_scan(lambda c, p: mamba_step(c, (p, None)), h, sub,
                               length=hi - lo)
        else:
            h, ys = maybe_scan(mamba_step, h, (sub, taus), length=hi - lo)
        if return_state:
            states.append(ys)

    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    w = params["embed"].T if cfg.tied_embeddings else params["lm_head"]
    logits = h @ w.astype(dt)
    logits = shard(logits, "batch", None, "vocab")
    if return_state:
        full = jax.tree_util.tree_map(
            lambda *a: jnp.concatenate(a, axis=0), *states) \
            if len(states) > 1 else states[0]
        st = {"conv": full["conv"], "ssm": full["ssm"],
              "pos": jnp.full((B,), S, jnp.int32)}
        if k:
            st["attn_k"] = jnp.stack([kv["k"] for kv in attn_kv])
            st["attn_v"] = jnp.stack([kv["v"] for kv in attn_kv])
        return logits, st
    return logits


def prefill(cfg: ModelConfig, params, tokens, S_max: int, **kw):
    """Parallel prefill: one forward over the prompt, states collected per
    layer (mamba conv/ssm finals + windowed shared-attn KV)."""
    B, S = tokens.shape
    eff = min(S_max, 4096) if cfg.hybrid_attn_every else S_max
    assert S <= eff or S % eff == 0, (S, eff)
    logits, state = forward(cfg, params, tokens, return_state=True,
                            S_max=S_max)
    return logits[:, -1:], state


def loss(cfg: ModelConfig, params, batch, *, sparsity=None, remat=None):
    from repro.models.transformer import softmax_xent
    tokens = batch["tokens"]
    logits = forward(cfg, params, tokens, sparsity=sparsity, remat=remat)
    l = softmax_xent(logits[:, :-1], tokens[:, 1:]).mean()
    return l, {"xent": l}


# --------------------------------------------------------------------- #
# Serving
# --------------------------------------------------------------------- #
def init_state(cfg: ModelConfig, B: int, S_max: int):
    d_in, H, hd, N, K = _dims(cfg)
    L = cfg.num_layers
    dt = dtype_of(cfg.dtype)
    st = {
        "conv": jnp.zeros((L, B, K - 1, d_in + 2 * N), dt),
        "ssm": jnp.zeros((L, B, H, hd, N), jnp.float32),
        "pos": jnp.zeros((B,), jnp.int32),
    }
    if cfg.hybrid_attn_every:
        n = _n_shared(cfg)
        KV, ahd = cfg.num_kv_heads, cfg.resolved_head_dim
        eff = min(S_max, 4096)          # shared-attn KV windowed for long ctx
        st["attn_k"] = jnp.zeros((n, B, eff, KV, ahd), dt)
        st["attn_v"] = jnp.zeros((n, B, eff, KV, ahd), dt)
    return st


def decode_step(cfg: ModelConfig, params, state, token):
    dt = dtype_of(cfg.dtype)
    B = token.shape[0]
    h = params["embed"].astype(dt)[token]
    pos = state["pos"]
    h0 = h                 # Zamba: shared block sees the current-token embedding
    k = cfg.hybrid_attn_every
    L = cfg.num_layers
    new_state = {"pos": pos + 1}

    def mamba_step(carry, xs):
        h = carry
        p, st = xs
        p = tfm._cast(p, dt)
        y, new_st = mamba_block(p, rmsnorm(h, p["ln"], cfg.norm_eps), cfg,
                                state=st)
        return h + y, new_st

    groups = [(g * k, min((g + 1) * k, L)) for g in range(_n_shared(cfg))] \
        if k else [(0, L)]
    new_conv, new_ssm = [], []
    attn = {"k": state["attn_k"], "v": state["attn_v"]} if k else None
    for gi, (lo, hi) in enumerate(groups):
        if k:
            h, attn = _shared_attn(cfg, params, h, h0, None, cache=attn,
                                   pos=pos, window=4096, site=gi)
        sub_p = jax.tree_util.tree_map(lambda a: a[lo:hi], params["mamba"])
        sub_st = {"conv": state["conv"][lo:hi], "ssm": state["ssm"][lo:hi]}
        h, sts = maybe_scan(mamba_step, h, (sub_p, sub_st), length=hi - lo)
        new_conv.append(sts["conv"])
        new_ssm.append(sts["ssm"])

    new_state["conv"] = jnp.concatenate(new_conv, axis=0)
    new_state["ssm"] = jnp.concatenate(new_ssm, axis=0)
    if k:
        new_state["attn_k"], new_state["attn_v"] = attn["k"], attn["v"]
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    w = params["embed"].T if cfg.tied_embeddings else params["lm_head"]
    return h @ w.astype(dt), new_state


