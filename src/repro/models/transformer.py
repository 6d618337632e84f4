"""Decoder-only / encoder-decoder transformer LM with stacked-layer scan.

Covers the dense/GQA, qk-norm, QKV-bias, sliding-window, MLA (DeepSeek-V3),
MoE (Mixtral / DeepSeek-V3) and whisper (enc-dec) variants of the assigned
pool. Parameters are stacked over the layer axis and the forward pass scans
over layers, keeping HLO size O(1) in depth (essential for the 95-layer
deepseek-67b dry-run).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.ctx import shard
from repro.models import moe as moe_lib
from repro.models.attention import (blockwise_attention, decode_attention,
                                    reference_attention)
from repro.models.common import (act_clip, activation, apply_rope, dense_init,
                                 dtype_of, embed_init, maybe_scan, rmsnorm,
                                 take_layer)

Params = Dict[str, Any]


def _cast(p, dt):
    """Cast f32 master weights to the compute dtype at point of use."""
    return jax.tree_util.tree_map(
        lambda a: a.astype(dt) if a.dtype == jnp.float32 else a, p)


# ===================================================================== #
# Init
# ===================================================================== #
def _attn_params(key, cfg: ModelConfig, L: int, cross: bool = False) -> Params:
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 8)
    if cfg.mla is not None and not cross:
        m = cfg.mla
        qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
        p = {
            "wq_a": dense_init(ks[0], (L, d, m.q_lora_rank)),
            "q_norm_a": jnp.ones((L, m.q_lora_rank)),
            "wq_b": dense_init(ks[1], (L, m.q_lora_rank, H * qk_dim)),
            "wkv_a": dense_init(ks[2], (L, d, m.kv_lora_rank + m.qk_rope_head_dim)),
            "kv_norm_a": jnp.ones((L, m.kv_lora_rank)),
            "wkv_b": dense_init(ks[3], (L, m.kv_lora_rank,
                                        H * (m.qk_nope_head_dim + m.v_head_dim))),
            "wo": dense_init(ks[4], (L, H * m.v_head_dim, d)),
        }
        return p
    p = {
        "wq": dense_init(ks[0], (L, d, H * hd)),
        "wk": dense_init(ks[1], (L, d, KV * hd)),
        "wv": dense_init(ks[2], (L, d, KV * hd)),
        "wo": dense_init(ks[3], (L, H * hd, d)),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((L, H * hd))
        p["bk"] = jnp.zeros((L, KV * hd))
        p["bv"] = jnp.zeros((L, KV * hd))
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.ones((L, hd))
        p["k_norm"] = jnp.ones((L, hd))
    return p


def _ffn_params(key, cfg: ModelConfig, L: int) -> Params:
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    if cfg.moe is not None:
        fe = cfg.moe.expert_d_ff or cfg.d_ff
        E = cfg.moe.num_experts
        p = {
            "router": dense_init(ks[0], (L, d, E)),
            "w_gate": dense_init(ks[1], (L, E, d, fe)),
            "w_up": dense_init(ks[2], (L, E, d, fe)),
            "w_down": dense_init(ks[3], (L, E, fe, d)),
        }
        if cfg.moe.num_shared_experts:
            fs = fe * cfg.moe.num_shared_experts
            p["shared_w_gate"] = dense_init(ks[4], (L, d, fs))
            p["shared_w_up"] = dense_init(ks[5], (L, d, fs))
            p["shared_w_down"] = dense_init(ks[6], (L, fs, d))
        return p
    return {
        "w_gate": dense_init(ks[0], (L, d, cfg.d_ff)),
        "w_up": dense_init(ks[1], (L, d, cfg.d_ff)),
        "w_down": dense_init(ks[2], (L, cfg.d_ff, d)),
    }


def _block_params(key, cfg: ModelConfig, L: int, cross: bool = False) -> Params:
    ka, kf, kc = jax.random.split(key, 3)
    p = {
        "ln1": jnp.ones((L, cfg.d_model)),
        "ln2": jnp.ones((L, cfg.d_model)),
        "attn": _attn_params(ka, cfg, L),
        "ffn": _ffn_params(kf, cfg, L),
    }
    if cross:
        p["ln_cross"] = jnp.ones((L, cfg.d_model))
        p["cross"] = _attn_params(kc, cfg, L, cross=True)
    return p


def init_params(cfg: ModelConfig, rng) -> Params:
    keys = jax.random.split(rng, 8)
    L = cfg.num_layers
    params: Params = {
        "embed": embed_init(keys[0], (cfg.vocab_size, cfg.d_model)),
        "blocks": _block_params(keys[1], cfg, L, cross=cfg.is_encoder_decoder),
        "final_norm": jnp.ones((cfg.d_model,)),
    }
    if not cfg.tied_embeddings:
        params["lm_head"] = dense_init(keys[2], (cfg.d_model, cfg.vocab_size))
    if cfg.is_encoder_decoder:
        params["enc_blocks"] = _block_params(keys[3], cfg, cfg.enc_layers)
        params["enc_norm"] = jnp.ones((cfg.d_model,))
        params["enc_pos"] = embed_init(keys[4], (cfg.num_frames, cfg.d_model))
        params["dec_pos"] = embed_init(keys[6], (4096, cfg.d_model))
    if cfg.mtp_depth:
        params["mtp"] = {
            "proj": dense_init(keys[5], (2 * cfg.d_model, cfg.d_model)),
            "block": _block_params(keys[7], cfg, cfg.mtp_depth),
            "norm": jnp.ones((cfg.d_model,)),
        }
    return params


# ===================================================================== #
# Attention (one layer, expanded form for train/prefill)
# ===================================================================== #
def _gqa_qkv(p, h, cfg: ModelConfig, positions):
    B, S, _ = h.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = h @ p["wq"]
    k = h @ p["wk"]
    v = h @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mla_qkv(p, h, cfg: ModelConfig, positions):
    """MLA expanded form. Returns q,k,v with head dims (nope+rope / v)."""
    m = cfg.mla
    B, S, _ = h.shape
    H = cfg.num_heads
    qa = rmsnorm(h @ p["wq_a"], p["q_norm_a"], cfg.norm_eps)
    q = (qa @ p["wq_b"]).reshape(B, S, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv_a = h @ p["wkv_a"]                                 # (B,S,kvr+rd)
    ckv, k_rope = jnp.split(kv_a, [m.kv_lora_rank], axis=-1)
    ckv = rmsnorm(ckv, p["kv_norm_a"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)  # shared head
    kv = (ckv @ p["wkv_b"]).reshape(B, S, H, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, (B, S, H, m.qk_rope_head_dim))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    return q, k, v, ckv, k_rope


def attention_block(p, h, cfg: ModelConfig, positions, *, causal=True,
                    attn_impl="blockwise_full", kv_override=None):
    """Self/cross attention sublayer (pre-norm residual outside)."""
    B, S, _ = h.shape
    if cfg.mla is not None and kv_override is None:
        q, k, v, _, _ = _mla_qkv(p, h, cfg, positions)
        o = blockwise_attention(q, k, v, causal=causal, window=cfg.attn_window,
                                impl=attn_impl)
        o = shard(o.reshape(B, S, -1), "batch", None, "heads")
        return o @ p["wo"]
    if kv_override is not None:                          # cross attention
        xk, xv = kv_override
        H, hd = cfg.num_heads, cfg.resolved_head_dim
        q = (h @ p["wq"]).reshape(B, S, H, hd)
        o = blockwise_attention(q, xk, xv, causal=False)
        return o.reshape(B, S, -1) @ p["wo"]
    q, k, v = _gqa_qkv(p, h, cfg, positions)
    q = shard(q, "batch", None, "heads", None)
    o = blockwise_attention(q, k, v, causal=causal, window=cfg.attn_window,
                            impl=attn_impl)
    o = shard(o.reshape(B, S, -1), "batch", None, "heads")
    return o @ p["wo"]


def ffn_block(p, h, cfg: ModelConfig, act_tau=None):
    B, S, d = h.shape
    if cfg.moe is not None:
        y, aux = moe_lib.moe_ffn(h.reshape(B * S, d), p, cfg.moe, cfg.act, act_tau)
        return y.reshape(B, S, d), aux
    act = activation(cfg.act)
    h_in = act_clip(h, act_tau)
    g = act(h_in @ p["w_gate"]) * (h_in @ p["w_up"])
    g = shard(g, "batch", None, "ff")
    g = act_clip(g, act_tau)
    return g @ p["w_down"], 0.0


# ===================================================================== #
# Forward (train / prefill share this; scan over stacked layers)
# ===================================================================== #
def _make_block_fn(cfg: ModelConfig, positions, *, causal, attn_impl,
                   enc_out=None, remat: Optional[str] = None):
    def block(h, xs):
        p, taus = xs
        p = _cast(p, h.dtype)
        a_tau = taus.get("attn") if taus else None
        f_tau = taus.get("ffn") if taus else None
        h = shard(h, "batch", None, "embed")
        x = rmsnorm(h, p["ln1"], cfg.norm_eps)
        x = act_clip(x, a_tau)
        h = h + attention_block(p["attn"], x, cfg, positions, causal=causal,
                                attn_impl=attn_impl)
        if enc_out is not None:
            x = rmsnorm(h, p["ln_cross"], cfg.norm_eps)
            h = h + attention_block(p["cross"], x, cfg, positions, causal=False,
                                    kv_override=enc_out)
        x = rmsnorm(h, p["ln2"], cfg.norm_eps)
        y, aux = ffn_block(p["ffn"], x, cfg, f_tau)
        return h + y, aux

    if remat == "full":
        block = jax.checkpoint(block)
    elif remat == "dots":
        block = jax.checkpoint(
            block, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return block


def _scan_blocks(block_fn, h, stacked_params, stacked_taus, L):
    def body(carry, xs):
        h = carry
        h, aux = block_fn(h, xs)
        return h, aux

    taus = stacked_taus if stacked_taus else None
    xs = (stacked_params, taus) if taus else (stacked_params, None)

    if taus is None:
        h, auxs = maybe_scan(lambda c, p: body(c, (p, None)),
                               h, stacked_params, length=L)
    else:
        h, auxs = maybe_scan(body, h, xs, length=L)
    return h, jnp.sum(auxs)


def encode(cfg: ModelConfig, params, frames, *, remat=None):
    """Whisper encoder: frames (B, F, d) precomputed by the stub frontend."""
    h = frames.astype(dtype_of(cfg.dtype)) + params["enc_pos"][None].astype(
        dtype_of(cfg.dtype))
    positions = jnp.arange(frames.shape[1])
    block_fn = _make_block_fn(cfg, positions, causal=False,
                              attn_impl="blockwise_full", remat=remat)
    h, _ = _scan_blocks(block_fn, h, params["enc_blocks"], None, cfg.enc_layers)
    return rmsnorm(h, params["enc_norm"], cfg.norm_eps)


def lm_forward(cfg: ModelConfig, params, tokens, *, frames=None,
               sparsity=None, attn_impl="blockwise_full", remat=None,
               q_offset=0) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (hidden, logits, aux_loss). tokens: (B, S) int32."""
    dt = dtype_of(cfg.dtype)
    h = params["embed"].astype(dt)[tokens]
    h = shard(h, "batch", None, "embed")
    positions = q_offset + jnp.arange(tokens.shape[1])

    enc_out = None
    if cfg.is_encoder_decoder:
        assert frames is not None, "whisper needs frame embeddings"
        e = encode(cfg, params, frames, remat=remat)
        B, F, _ = e.shape
        H, hd = cfg.num_heads, cfg.resolved_head_dim
        # Cross K/V computed per-layer from enc_out inside the scanned block.
        h = h + params["dec_pos"].astype(dt)[jnp.clip(positions, 0, 4095)]
        enc_out = e

    if enc_out is not None:
        # cross attention needs per-layer K/V from enc_out; wrap block fn
        def make(enc):
            def blk(h, xs):
                p, taus = xs
                B, S, _ = h.shape
                KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
                xk = (enc @ p["cross"]["wk"]).reshape(B, enc.shape[1], KV, hd)
                xv = (enc @ p["cross"]["wv"]).reshape(B, enc.shape[1], KV, hd)
                base = _make_block_fn(cfg, positions, causal=True,
                                      attn_impl=attn_impl, enc_out=(xk, xv))
                return base(h, xs)
            return jax.checkpoint(blk) if remat else blk
        block_fn = make(enc_out)
    else:
        block_fn = _make_block_fn(cfg, positions, causal=True,
                                  attn_impl=attn_impl, remat=remat)

    h, aux = _scan_blocks(block_fn, h, params["blocks"], sparsity, cfg.num_layers)
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = unembed(cfg, params, h)
    return h, logits, aux


def unembed(cfg: ModelConfig, params, h):
    w = params["embed"].T if cfg.tied_embeddings else params["lm_head"]
    logits = h @ w.astype(h.dtype)
    return shard(logits, "batch", None, "vocab")


# ===================================================================== #
# Loss (+ MTP)
# ===================================================================== #
def softmax_xent(logits, labels):
    """Numerically-stable CE in f32; logits (…, V), labels (…,) int."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - gold


def lm_loss(cfg: ModelConfig, params, batch, *, sparsity=None,
            attn_impl="blockwise_full", remat=None):
    """Full-sequence forward (keeps S a power of two); loss on S-1 shifts."""
    tokens = batch["tokens"]
    frames = batch.get("frames")
    h, logits, aux = lm_forward(cfg, params, tokens, frames=frames,
                                sparsity=sparsity, attn_impl=attn_impl,
                                remat=remat)
    loss = softmax_xent(logits[:, :-1], tokens[:, 1:]).mean()
    metrics = {"xent": loss, "aux": aux}

    if cfg.mtp_depth:                            # predict token t+2 from h_t
        dt = h.dtype
        nxt_emb = params["embed"].astype(dt)[jnp.roll(tokens, -1, axis=1)]
        z = jnp.concatenate([rmsnorm(h, params["mtp"]["norm"], cfg.norm_eps),
                             nxt_emb], axis=-1) @ params["mtp"]["proj"].astype(dt)
        positions = jnp.arange(z.shape[1])
        blk = _make_block_fn(cfg, positions, causal=True, attn_impl=attn_impl,
                             remat=remat)
        z, _ = _scan_blocks(blk, z, params["mtp"]["block"], None, cfg.mtp_depth)
        z = rmsnorm(z, params["final_norm"], cfg.norm_eps)
        mtp_logits = unembed(cfg, params, z[:, :-2])
        mtp_loss = softmax_xent(mtp_logits, tokens[:, 2:]).mean()
        metrics["mtp"] = mtp_loss
        loss = loss + 0.1 * mtp_loss
    return loss + aux, metrics


# ===================================================================== #
# Serving: prefill + single-token decode with KV caches
# ===================================================================== #
def init_cache(cfg: ModelConfig, B: int, S_max: int) -> Params:
    dt = dtype_of(cfg.dtype)
    L = cfg.num_layers
    eff = min(S_max, cfg.attn_window) if cfg.attn_window else S_max
    if cfg.mla is not None:
        m = cfg.mla
        cache = {
            "ckv": jnp.zeros((L, B, eff, m.kv_lora_rank), dt),
            "krope": jnp.zeros((L, B, eff, m.qk_rope_head_dim), dt),
        }
    else:
        KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        cache = {
            "k": jnp.zeros((L, B, eff, KV, hd), dt),
            "v": jnp.zeros((L, B, eff, KV, hd), dt),
        }
    cache["pos"] = jnp.zeros((B,), jnp.int32)     # true next position (rope)
    if cfg.is_encoder_decoder:
        KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        cache["xk"] = jnp.zeros((L, B, cfg.num_frames, KV, hd), dt)
        cache["xv"] = jnp.zeros((L, B, cfg.num_frames, KV, hd), dt)
    return cache


def _cache_write(buf, i, new, slot):
    """buf (L,B,S,...), new (B,1,...): write ``new[b]`` into layer ``i`` at
    position ``slot[b]`` of each sequence b. Returns the updated buffer and
    layer i's (B,S,...) slice read from it.

    Only the B new rows are written, straight into the full buffer that
    ``decode_step`` carries through its layer scan, so that with the cache
    donated to the jitted step (``ServeSession``) XLA updates it in place.
    A slot past the buffer (MLA decoding beyond S_max) writes nothing."""
    B = new.shape[0]
    buf = buf.at[i, jnp.arange(B), slot].set(new[:, 0].astype(buf.dtype))
    return buf, jax.lax.dynamic_index_in_dim(buf, i, 0, keepdims=False)


def decode_step(cfg: ModelConfig, params, cache, token):
    """token: (B, 1) int32. Returns (logits (B,1,V), new_cache).

    Each layer writes its one new K/V (or latent) row per sequence into the
    carried cache (``_cache_write``); jit this step with the cache donated
    and the update is in place."""
    dt = dtype_of(cfg.dtype)
    B = token.shape[0]
    h = params["embed"].astype(dt)[token]                     # (B,1,d)
    pos = cache["pos"]
    window = cfg.attn_window

    if cfg.is_encoder_decoder:
        h = h + params["dec_pos"].astype(dt)[jnp.clip(pos, 0, 4095)][:, None]

    # The cache rides in the scan's carry and each layer writes its rows
    # into it: collecting per-layer caches as scan outputs would stack them
    # into a SECOND full-cache buffer, defeating donation.
    def layer_carry(carry, xs):
        h, caches = carry
        p, i = xs
        p = _cast(p, h.dtype)
        x = rmsnorm(h, p["ln1"], cfg.norm_eps)
        if cfg.mla is not None:
            o, caches = _mla_decode_attn(p["attn"], x, cfg, caches, i, pos)
        else:
            o, caches = _gqa_decode_attn(p["attn"], x, cfg, caches, i, pos,
                                         window)
        h = h + o
        if cfg.is_encoder_decoder:                # xk/xv: read-only here
            x = rmsnorm(h, p["ln_cross"], cfg.norm_eps)
            q = (x @ p["cross"]["wq"]).reshape(B, 1, cfg.num_heads,
                                               cfg.resolved_head_dim)
            xk, xv = (jax.lax.dynamic_index_in_dim(caches[n], i, 0,
                                                   keepdims=False)
                      for n in ("xk", "xv"))
            xo = decode_attention(q, xk, xv, jnp.full((B,), cfg.num_frames))
            h = h + xo.reshape(B, 1, -1) @ p["cross"]["wo"]
        x = rmsnorm(h, p["ln2"], cfg.norm_eps)
        y, _ = ffn_block(p["ffn"], x, cfg)
        return (h + y, caches), None

    layer_caches = {k: v for k, v in cache.items() if k != "pos"}
    (h, new_caches), _ = maybe_scan(
        layer_carry, (h, layer_caches),
        (params["blocks"], jnp.arange(cfg.num_layers)),
        length=cfg.num_layers)
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = unembed(cfg, params, h)
    new_cache = dict(new_caches)
    new_cache["pos"] = pos + 1
    return logits, new_cache


def _gqa_decode_attn(p, x, cfg, caches, i, pos, window):
    B = x.shape[0]
    KV, hd, H = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.num_heads
    q, k, v = _gqa_qkv(p, x, cfg, pos[:, None])
    S = caches["k"].shape[2]
    slot = pos % S                        # ring buffer (id when S covers pos)
    kbuf, new_k = _cache_write(caches["k"], i, k, slot)
    vbuf, new_v = _cache_write(caches["v"], i, v, slot)
    eff_len = jnp.minimum(pos + 1, S)
    o = decode_attention(q, new_k, new_v, eff_len)
    o = o.reshape(B, 1, H * hd)
    return o @ p["wo"], dict(caches, k=kbuf, v=vbuf)


def _mla_decode_attn(p, x, cfg, caches, i, pos):
    """Absorbed-form MLA decode: cache latent ckv + shared k_rope."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.num_heads
    qa = rmsnorm(x @ p["wq_a"], p["q_norm_a"], cfg.norm_eps)
    q = (qa @ p["wq_b"]).reshape(B, 1, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, pos[:, None], cfg.rope_theta)

    kv_a = x @ p["wkv_a"]
    ckv, k_rope = jnp.split(kv_a, [m.kv_lora_rank], axis=-1)
    ckv = rmsnorm(ckv, p["kv_norm_a"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], pos[:, None], cfg.rope_theta)[:, :, 0]

    ckv_buf, new_ckv = _cache_write(caches["ckv"], i, ckv, pos)  # (B,S,kvr)
    krope_buf, new_krope = _cache_write(caches["krope"], i, k_rope, pos)

    wkv_b = p["wkv_b"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim + m.v_head_dim)
    wk_b, wv_b = wkv_b[..., :m.qk_nope_head_dim], wkv_b[..., m.qk_nope_head_dim:]
    # absorb: q_eff = q_nope @ wk_b^T  -> latent space
    q_eff = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0].astype(jnp.float32),
                       wk_b.astype(jnp.float32))              # (B,H,kvr)
    scale = 1.0 / jnp.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    s = (jnp.einsum("bhr,bsr->bhs", q_eff, new_ckv.astype(jnp.float32)) +
         jnp.einsum("bhn,bsn->bhs", q_rope[:, 0].astype(jnp.float32),
                    new_krope.astype(jnp.float32))) * scale
    S = new_ckv.shape[1]
    valid = jnp.arange(S)[None, :] < (pos + 1)[:, None]
    s = jnp.where(valid[:, None, :], s, -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    lat = jnp.einsum("bhs,bsr->bhr", pr, new_ckv.astype(jnp.float32))
    o = jnp.einsum("bhr,rhv->bhv", lat, wv_b.astype(jnp.float32))  # (B,H,v)
    o = o.reshape(B, 1, H * m.v_head_dim).astype(x.dtype)
    return o @ p["wo"], dict(caches, ckv=ckv_buf, krope=krope_buf)


def _window(a, eff: int):
    """K/V (B, S, ...) of a prompt as a cache of ``eff`` positions: the last
    ``eff`` (position p at slot p % eff when S is a multiple of eff), or
    the prompt right-padded with zeros."""
    if a.shape[1] >= eff:
        return a[:, -eff:]
    pad = [(0, 0)] * a.ndim
    pad[1] = (0, eff - a.shape[1])
    return jnp.pad(a, pad)


def prefill(cfg: ModelConfig, params, tokens, S_max: int, *, frames=None,
            attn_impl="blockwise_full", sparsity=None, prompt_lens=None):
    """Run the full prompt, build the cache. Returns (last_logits, cache).

    ``prompt_lens`` (B,) serves a ragged batch padded on the right to the
    chunk max: logits are gathered at each row's last real token
    (``lens[b] - 1``; causal attention never looks right, so the pad
    columns cannot leak in) and ``cache["pos"]`` starts at ``lens`` — the
    decode steps overwrite the pad rows' cache slots and mask past
    ``pos``, exactly the "pad to max then mask" batching discipline."""
    B, S = tokens.shape
    dt = dtype_of(cfg.dtype)
    cache = init_cache(cfg, B, S_max)
    h = params["embed"].astype(dt)[tokens]
    positions = jnp.arange(S)

    enc = None
    if cfg.is_encoder_decoder:
        enc = encode(cfg, params, frames)
        h = h + params["dec_pos"].astype(dt)[jnp.clip(positions, 0, 4095)]

    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    eff = cache["k"].shape[2] if "k" in cache else cache["ckv"].shape[2]
    assert S <= eff or S % eff == 0, (
        "ring-buffer slot arithmetic needs prompt len < cache or a multiple "
        f"of the window; got S={S}, eff={eff}")

    def layer(h, xs):
        p, taus = xs
        p = _cast(p, h.dtype)
        f_tau = taus.get("ffn") if taus else None
        a_tau = taus.get("attn") if taus else None
        x = rmsnorm(h, p["ln1"], cfg.norm_eps)
        x = act_clip(x, a_tau)
        if cfg.mla is not None:
            q, k, v, ckv, k_rope = _mla_qkv(p["attn"], x, cfg, positions)
            o = blockwise_attention(q, k, v, causal=True, impl=attn_impl)
            o = o.reshape(B, S, -1) @ p["attn"]["wo"]
            lc = {"ckv": _window(ckv, eff),
                  "krope": _window(k_rope[:, :, 0], eff)}
        else:
            q, k, v = _gqa_qkv(p["attn"], x, cfg, positions)
            o = blockwise_attention(q, k, v, causal=True,
                                    window=cfg.attn_window, impl=attn_impl)
            o = o.reshape(B, S, -1) @ p["attn"]["wo"]
            lc = {"k": _window(k, eff), "v": _window(v, eff)}
        h = h + o
        if cfg.is_encoder_decoder:
            x = rmsnorm(h, p["ln_cross"], cfg.norm_eps)
            xk = (enc @ p["cross"]["wk"]).reshape(B, enc.shape[1], KV, hd)
            xv = (enc @ p["cross"]["wv"]).reshape(B, enc.shape[1], KV, hd)
            h = h + attention_block(p["cross"], x, cfg, positions, causal=False,
                                    kv_override=(xk, xv))
            lc["xk"], lc["xv"] = xk, xv
        x = rmsnorm(h, p["ln2"], cfg.norm_eps)
        y, _ = ffn_block(p["ffn"], x, cfg, f_tau)
        return h + y, lc

    xs = (params["blocks"], sparsity) if sparsity else (params["blocks"], None)
    if sparsity:
        h, layer_caches = maybe_scan(layer, h, xs, length=cfg.num_layers)
    else:
        h, layer_caches = maybe_scan(lambda c, p: layer(c, (p, None)),
                                       h, params["blocks"],
                                       length=cfg.num_layers)
    for k_, v_ in layer_caches.items():
        cache[k_] = v_
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    if prompt_lens is None:
        cache["pos"] = jnp.full((B,), S, jnp.int32)
        return unembed(cfg, params, h[:, -1:]), cache
    assert S <= eff, (
        "ragged prefill (prompt_lens) needs the whole padded prompt "
        f"resident in the cache window; got S={S}, eff={eff}")
    lens = jnp.asarray(prompt_lens, jnp.int32)
    cache["pos"] = lens
    last = jnp.take_along_axis(h, (lens - 1)[:, None, None], axis=1)
    return unembed(cfg, params, last), cache
