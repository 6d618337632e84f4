"""Mamba2 layers and the Zamba2 hybrid, after transformers'
``modeling_zamba2.py`` (``Zamba2MambaMixer.torch_forward``,
``Zamba2MambaDecoderLayer``, ``Zamba2HybridLayer``,
``Zamba2AttentionDecoderLayer``, ``Zamba2MLP``).

Mamba2 layer, heads h with state in R^{hd x N}, B and C in ``ngroups``
groups (head h reads group h // (H / ngroups)):
    [z | xBC | dt] = x W_in;   xBC = silu(causal_conv(xBC) + b)  (width K)
    dt = max(softplus(dt + dt_bias), DT_MIN);   a = exp(-dt * exp(A_log))
    H_t = a_t H_{t-1} + (dt_t x_t) ⊗ B_t;   y_t = H_t C_t + D x_t
    out = (rmsnorm_per_group(y * silu(z)) * w) W_out
Layer i: h <- h + mamba_i(rmsnorm(h + t_i)); t_i = 0 except at the sites.

Zamba2 hybrid: before layer ``hybrid_layer_ids[j]`` (site j) the
weight-tied transformer block j % ``num_mem_blocks`` runs, with no residual
of its own:
    x = rmsnorm(concat(h, h_emb))                      (2 * d_model wide)
    o = attention(x) W_o   (rotary positions, scale (head_dim / 2)^-0.5)
    x = rmsnorm(o);   [g | u] = x W_gu + (x A_j) B_j   (site j's adapter)
    t_j = ((gelu(g) * u) W_down) W_lin_j               (site j's ``linear``)
where h_emb is the token's embedding. Each site keeps its own KV cache.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.ctx import shard
from repro.models import transformer as tfm
from repro.models.attention import NEG_INF, blockwise_attention
from repro.models.common import (act_clip, activation, dense_init, dtype_of,
                                 embed_init, maybe_scan, rmsnorm)

Params = Dict[str, Any]

DT_MIN = 1e-3            # Zamba2's ``time_step_min``: dt's floor
GATED_NORM_EPS = 1e-5    # ``Zamba2RMSNormGated``'s eps, fixed in the source
ATTN_WINDOW = 4096       # the shared blocks' KV is kept for the last 4096
                         # positions, Zamba2's context (long_500k dry-run)


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    return d_in, d_in // s.head_dim, s.head_dim, s.state_dim, s.conv_dim, \
        s.ngroups


def segments(cfg: ModelConfig):
    """The (first, end) layers of the stack's segments: it is cut before
    every site of ``hybrid_layer_ids``."""
    cuts = sorted({0, cfg.num_layers, *cfg.hybrid_layer_ids})
    return list(zip(cuts[:-1], cuts[1:]))


def _take(tree, i):
    """Layer ``i`` (a traced index) of a stacked pytree, read in place."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)


# --------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------- #
def init_mamba_params(cfg: ModelConfig, rng, L: int) -> Params:
    d = cfg.d_model
    d_in, H, hd, N, K, G = _dims(cfg)
    conv_ch = d_in + 2 * G * N
    ks = jax.random.split(rng, 8)
    return {
        "ln": jnp.ones((L, d)),
        "in_proj": dense_init(ks[0], (L, d, d_in + conv_ch + H)),  # z|xBC|dt
        "conv_w": dense_init(ks[1], (L, K, conv_ch), in_axis=-2),
        "conv_b": jnp.zeros((L, conv_ch)),
        "A_log": jnp.zeros((L, H)),
        "D": jnp.ones((L, H)),
        "dt_bias": jnp.zeros((L, H)),
        "out_norm": jnp.ones((L, d_in)),
        "out_proj": dense_init(ks[2], (L, d_in, d)),
    }


def init_hybrid_params(cfg: ModelConfig, rng) -> Params:
    """The shared blocks, stacked (num_mem_blocks, ...), and each site's
    adapter and ``linear``, stacked (len(hybrid_layer_ids), ...)."""
    M, n = cfg.num_mem_blocks, len(cfg.hybrid_layer_ids)
    d, f, r = cfg.d_model, cfg.d_ff, cfg.adapter_rank
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(rng, 9)
    shared = {
        "ln1": jnp.ones((M, 2 * d)),
        "attn": {"wq": dense_init(ks[0], (M, 2 * d, H * hd)),
                 "wk": dense_init(ks[1], (M, 2 * d, KV * hd)),
                 "wv": dense_init(ks[2], (M, 2 * d, KV * hd)),
                 "wo": dense_init(ks[3], (M, H * hd, d))},
        "ln2": jnp.ones((M, d)),
        "w_gate_up": dense_init(ks[4], (M, d, 2 * f)),
        "w_down": dense_init(ks[5], (M, f, d)),
    }
    sites = {"adapter_a": dense_init(ks[6], (n, d, r)),
             "adapter_b": dense_init(ks[7], (n, r, 2 * f)),
             "linear": dense_init(ks[8], (n, d, d))}
    return {"shared": shared, "sites": sites}


def init_params(cfg: ModelConfig, rng) -> Params:
    ks = jax.random.split(rng, 4)
    p: Params = {
        "embed": embed_init(ks[0], (cfg.vocab_size, cfg.d_model)),
        "mamba": init_mamba_params(cfg, ks[1], cfg.num_layers),
        "final_norm": jnp.ones((cfg.d_model,)),
    }
    if cfg.hybrid_layer_ids:
        p.update(init_hybrid_params(cfg, ks[2]))
    if not cfg.tied_embeddings:
        p["lm_head"] = dense_init(ks[3], (cfg.d_model, cfg.vocab_size))
    return p


# --------------------------------------------------------------------- #
# Mamba2 layer
# --------------------------------------------------------------------- #
def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv, then silu. x:(B,S,C), w:(K,C), state:(B,K-1,C)
    the K-1 inputs before x (zeros when None). Returns (out, the last K-1
    inputs: the next conv state)."""
    K = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)   # (B,S+K-1,C)
    f32 = xp.astype(jnp.float32)
    out = sum(f32[:, i:i + x.shape[1]] * w[i].astype(jnp.float32)
              for i in range(K)) + b.astype(jnp.float32)
    return jax.nn.silu(out).astype(x.dtype), xp[:, -(K - 1):]


def _gated_rmsnorm(y, z, w, groups: int):
    """``Zamba2RMSNormGated``: y * silu(z), normalised over each of
    ``groups`` equal slices of the last axis, times w (float32)."""
    g = y * jax.nn.silu(z.astype(jnp.float32))
    g = g.reshape(*g.shape[:-1], groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + GATED_NORM_EPS)
    return g.reshape(y.shape) * w.astype(jnp.float32)


def mamba_block(p, x, cfg: ModelConfig, state=None, act_tau=None):
    """x: (B,S,d). state: {'conv': (B,K-1,C), 'ssm': (B,H,hd,N)} the state
    before x, or None (zeros). Returns (out, the state after x)."""
    B, S, _ = x.shape
    d_in, H, hd, N, K, G = _dims(cfg)
    f32 = jnp.float32
    x = act_clip(x, act_tau)
    z, xbc, dt = jnp.split(x @ p["in_proj"], [d_in, 2 * d_in + 2 * G * N],
                           axis=-1)
    xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"],
                                 state["conv"] if state else None)
    xs, Bc, Cc = jnp.split(xbc, [d_in, d_in + G * N], axis=-1)
    dt = jnp.maximum(jax.nn.softplus(dt.astype(f32)
                                     + p["dt_bias"].astype(f32)), DT_MIN)
    a = jnp.exp(-dt * jnp.exp(p["A_log"].astype(f32)))              # (B,S,H)
    xs = xs.astype(f32).reshape(B, S, G, H // G, hd)
    dx = dt.reshape(B, S, G, H // G, 1) * xs
    Bc = Bc.astype(f32).reshape(B, S, G, N)
    Cc = Cc.astype(f32).reshape(B, S, G, N)

    def step(st, inp):                      # st: (B,G,H/G,hd,N)
        a_t, dx_t, B_t, C_t = inp
        st = a_t[..., None, None] * st + \
            dx_t[..., None] * B_t[:, :, None, None, :]
        return st, jnp.einsum("bgkdn,bgn->bgkd", st, C_t)

    st0 = state["ssm"].reshape(B, G, H // G, hd, N) if state else \
        jnp.zeros((B, G, H // G, hd, N), f32)
    a = a.reshape(B, S, G, H // G)
    if S == 1:                              # decode: one step, no loop
        st, y = step(st0, (a[:, 0], dx[:, 0], Bc[:, 0], Cc[:, 0]))
        y = y[:, None]
    else:
        st, ys = maybe_scan(step, st0, tuple(jnp.moveaxis(v, 1, 0)
                                             for v in (a, dx, Bc, Cc)))
        y = jnp.moveaxis(ys, 0, 1)                          # (B,S,G,H/G,hd)
    y = y + p["D"].astype(f32).reshape(G, H // G, 1) * xs
    y = _gated_rmsnorm(y.reshape(B, S, d_in), z, p["out_norm"], G)
    y = shard(y.astype(x.dtype), "batch", None, "ff")
    return y @ p["out_proj"], {"conv": new_conv,
                               "ssm": st.reshape(B, H, hd, N)}


# --------------------------------------------------------------------- #
# Shared transformer block
# --------------------------------------------------------------------- #
def _site_decode_attn(q, k, v, kv, j, pos, scale):
    """One token's attention at site j. q (B,1,H,hd), k/v (B,1,KV,hd);
    ``kv`` buffers (sites, B, S, KV * hd), in which each sequence's new row
    is written at slot ``pos % S``. Returns (o (B,1,H * hd), kv).

    The cache keeps each position's heads side by side in one row: with a
    head of 224 a (.., KV, hd) buffer is stored by the chip with positions
    minor, which the row write cannot use, so every step would copy the
    whole buffer into a row layout and back. Each head's query is laid out
    instead over its own kv head's columns of a row (zeros elsewhere), so
    the scores and the weighted values are plain contractions over rows."""
    B, _, H, hd = q.shape
    KV = k.shape[2]
    S = kv["k"].shape[2]
    f32 = jnp.float32
    rows = jnp.arange(B)
    kv = {n: kv[n].at[j, rows, pos % S].set(a.reshape(B, -1).astype(
        kv[n].dtype)) for n, a in (("k", k), ("v", v))}
    own = jnp.repeat(jnp.eye(KV, dtype=f32), hd, axis=1)     # (KV, KV*hd)
    own = jnp.repeat(own, H // KV, axis=0)                    # (H, KV*hd)
    qq = jnp.tile(q[:, 0].astype(f32) * scale, (1, 1, KV)) * own
    s = jnp.einsum("bhe,bse->bhs", qq, kv["k"][j].astype(f32))
    valid = jnp.arange(S)[None, :] < jnp.minimum(pos + 1, S)[:, None]
    p = jax.nn.softmax(jnp.where(valid[:, None], s, NEG_INF), axis=-1)
    o = jnp.einsum("bhs,bse->bhe", p, kv["v"][j].astype(f32)) * own
    o = o.reshape(B, H, KV, hd).sum(axis=2)
    return o.reshape(B, 1, H * hd).astype(q.dtype), kv


def _shared_block(cfg: ModelConfig, params, j, h, emb, positions,
                  kv=None, pos=None):
    """Site j (static): block j % num_mem_blocks with site j's adapter and
    linear. Returns (t_j, kv). ``kv`` {'k','v'}: (sites, B, S_max, KV * hd),
    or empty (no cache); prefill (``pos`` None) writes the prompt's K/V at
    site j, decode (``pos`` the (B,) positions, h one token) writes one row
    per sequence."""
    dt = h.dtype
    blk = tfm._cast(jax.tree_util.tree_map(
        lambda a: a[j % cfg.num_mem_blocks], params["shared"]), dt)
    site = tfm._cast(jax.tree_util.tree_map(lambda a: a[j], params["sites"]),
                     dt)
    scale = (cfg.resolved_head_dim / 2) ** -0.5
    B, S, _ = h.shape
    x = rmsnorm(jnp.concatenate([h, emb], axis=-1), blk["ln1"], cfg.norm_eps)
    if pos is None:
        q, k, v = tfm._gqa_qkv(blk["attn"], x, cfg, positions)
        o = blockwise_attention(q, k, v, causal=True, scale=scale)
        o = o.reshape(B, S, -1) @ blk["attn"]["wo"]
        if kv:
            eff = kv["k"].shape[2]
            kv = {n: kv[n].at[j].set(tfm._window(a, eff).reshape(
                B, eff, -1).astype(kv[n].dtype))
                for n, a in (("k", k), ("v", v))}
    else:
        q, k, v = tfm._gqa_qkv(blk["attn"], x, cfg, pos[:, None])
        o, kv = _site_decode_attn(q, k, v, kv, j, pos, scale)
        o = o @ blk["attn"]["wo"]
    x = rmsnorm(o, blk["ln2"], cfg.norm_eps)
    gu = x @ blk["w_gate_up"] + (x @ site["adapter_a"]) @ site["adapter_b"]
    g, u = jnp.split(gu, 2, axis=-1)
    y = (activation(cfg.act)(g) * u) @ blk["w_down"]
    return y @ site["linear"], kv


# --------------------------------------------------------------------- #
# The layer stack
# --------------------------------------------------------------------- #
def _stack(cfg: ModelConfig, params, tokens, positions, state=None,
           decode=False, sparsity=None, remat=None):
    """Embedding and every layer. The stack is cut before each site
    (``segments``): a segment that starts at site j first runs site j's
    shared block, whose output t_j enters the segment's first layer, and
    the segment's layers run in one scan. ``state`` (serving) holds each
    segment's conv and SSM states, (layers, B, ...), which ride in its
    scan's carry, and the sites' KV; each layer and each site writes its
    own slice in place, as ``transformer.decode_step`` does with its cache.
    ``decode``: one token per sequence at ``state['pos']``. Returns (h
    before the final norm, new state)."""
    dt = dtype_of(cfg.dtype)
    h = params["embed"].astype(dt)[tokens]
    h = shard(h, "batch", None, "embed")
    emb = h                             # the shared blocks' second input
    pos = state["pos"] if decode else None
    ids = tuple(cfg.hybrid_layer_ids)
    kv = {n: state[n] for n in ("k", "v") if state and n in state}
    recurrent = {n: [] for n in ("conv", "ssm") if state and n in state}

    def layer(carry, i):                # i: (the layer, its row in own)
        h, t, own = carry
        p = tfm._cast(_take(params["mamba"], i[0]), dt)
        tau = _take(sparsity, i[0]).get("ffn") if sparsity else None
        with jax.named_scope("mamba2"):
            y, new = mamba_block(p, rmsnorm(h + t, p["ln"], cfg.norm_eps),
                                 cfg, state=_take(own, i[1]) if own else None,
                                 act_tau=tau)
        own = {n: jax.lax.dynamic_update_index_in_dim(
            a, new[n].astype(a.dtype), i[1], 0) for n, a in own.items()}
        return (h + y, jnp.zeros_like(t), own), None

    if remat:
        layer = jax.checkpoint(layer)
    for seg, (lo, hi) in enumerate(segments(cfg)):
        t = jnp.zeros_like(h)
        if lo in ids:
            with jax.named_scope("shared_block"):
                t, kv = _shared_block(cfg, params, ids.index(lo), h, emb,
                                      positions, kv, pos)
        own = {n: state[n][seg] for n in recurrent}
        (h, _, own), _ = maybe_scan(layer, (h, t, own),
                                    (jnp.arange(lo, hi), jnp.arange(hi - lo)))
        for n in recurrent:
            recurrent[n].append(own[n])
    return h, {**{n: tuple(v) for n, v in recurrent.items()}, **kv}


def _logits(cfg: ModelConfig, params, h):
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    w = params["embed"].T if cfg.tied_embeddings else params["lm_head"]
    return shard(h @ w.astype(h.dtype), "batch", None, "vocab")


def forward(cfg: ModelConfig, params, tokens, *, sparsity=None, remat=None):
    """Teacher-forced logits (B, S, V) from zero state."""
    h, _ = _stack(cfg, params, tokens, jnp.arange(tokens.shape[1]),
                  sparsity=sparsity, remat=remat)
    return _logits(cfg, params, h)


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
    d_in, H, hd, N, K, G = _dims(cfg)
    L = cfg.num_layers
    dt = dtype_of(cfg.dtype)
    segs = [hi - lo for lo, hi in segments(cfg)]
    st = {
        "conv": tuple(jnp.zeros((n, B, K - 1, d_in + 2 * G * N), dt)
                      for n in segs),
        "ssm": tuple(jnp.zeros((n, B, H, hd, N), jnp.float32) for n in segs),
        "pos": jnp.zeros((B,), jnp.int32),
    }
    if cfg.hybrid_layer_ids:
        n = len(cfg.hybrid_layer_ids)
        KV, ahd = cfg.num_kv_heads, cfg.resolved_head_dim
        eff = min(S_max, ATTN_WINDOW)
        st["k"] = jnp.zeros((n, B, eff, KV * ahd), dt)
        st["v"] = jnp.zeros((n, B, eff, KV * ahd), dt)
    return st


@functools.partial(jax.jit, static_argnums=(0, 3))
def prefill(cfg: ModelConfig, params, tokens, S_max: int):
    """The prompt through every layer at once (the recurrence stepped over
    its positions), from zero state. Returns (last logits (B,1,V), state)."""
    B, S = tokens.shape
    eff = min(S_max, ATTN_WINDOW)
    assert S <= eff or S % eff == 0, (S, eff)
    h, state = _stack(cfg, params, tokens, jnp.arange(S),
                      state=init_state(cfg, B, S_max))
    state["pos"] = jnp.full((B,), S, jnp.int32)
    return _logits(cfg, params, h[:, -1:]), state


def decode_step(cfg: ModelConfig, params, state, token):
    """token: (B, 1). Every layer writes its new conv and SSM state, and
    every site its new K/V rows, into the state it was given (in place when
    the state is donated, as ``ServeSession`` does)."""
    h, new = _stack(cfg, params, token, state["pos"][:, None], state=state,
                    decode=True)
    new["pos"] = state["pos"] + 1
    return _logits(cfg, params, h), new
