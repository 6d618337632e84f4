"""Attention implementations.

``blockwise_attention`` is a flash-style, memory-bounded attention written in
pure JAX (lax.scan over KV blocks with an online softmax). It keeps compiled
peak memory at O(S·d + S·block_k) instead of O(S^2) so the 32k prefill cells
lower with sane memory. FLOPs remain O(S^2) in the baseline ("blockwise_full");
the banded variant ("banded") skips fully-masked KV blocks via a static
(q-block, kv-block) pair table — the same static-schedule idea the paper uses
for weight tiles, applied to the causal/window structure. The banded variant is
a beyond-paper §Perf optimization and the default for sliding-window models.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.ctx import shard
from repro.models.common import maybe_scan

NEG_INF = -1e30


def _mask_bias(q_pos, k_pos, causal: bool, window: int):
    """(Q, K) additive bias from causal/window structure."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        ok &= q_pos[:, None] >= k_pos[None, :]
    if window > 0:
        ok &= q_pos[:, None] - k_pos[None, :] < window
    return jnp.where(ok, 0.0, NEG_INF)


def reference_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                        kv_len: Optional[jnp.ndarray] = None, scale=None):
    """Naive O(S^2)-memory oracle. q:(B,Sq,H,D) k,v:(B,Sk,KV,D). ``scale``
    multiplies the scores (default 1/sqrt(D))."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    Dv = v.shape[-1]
    G = H // KV
    qq = q.reshape(B, Sq, KV, G, D)
    if scale is None:
        scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qq.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(k.shape[1])
    bias = _mask_bias(q_pos, k_pos, causal, window)
    if kv_len is not None:                       # per-sequence valid length
        valid = k_pos[None, :] < kv_len[:, None]             # (B, Sk)
        bias = bias[None] + jnp.where(valid, 0.0, NEG_INF)[:, None]
        s = s + bias[:, None, None]
    else:
        s = s + bias
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, Dv).astype(q.dtype)


def blockwise_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                        block_k=512, kv_len: Optional[jnp.ndarray] = None,
                        impl="blockwise_full", scale=None):
    """Flash-style attention. q:(B,Sq,H,D) k,v:(B,Sk,KV,D) -> (B,Sq,H,D).
    ``scale`` multiplies the scores (default 1/sqrt(D)).

    impl:
      blockwise_full  scan over every KV block, masking (baseline)
      banded          scan only KV blocks that intersect the causal/window band
    """
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    if Sk <= block_k * 2:
        return reference_attention(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset, kv_len=kv_len,
                                   scale=scale)
    if Sk % block_k:                                  # pad ragged KV, mask tail
        pad = block_k - Sk % block_k
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if kv_len is None:
            kv_len = jnp.full((B,), Sk, jnp.int32)
        Sk = Sk + pad
    G = H // KV
    nkb = Sk // block_k
    if scale is None:
        qq = (q.reshape(B, Sq, KV, G, D).astype(jnp.float32)
              / jnp.sqrt(D).astype(jnp.float32))
    else:
        qq = q.reshape(B, Sq, KV, G, D).astype(jnp.float32) * scale
    q_pos = q_offset + jnp.arange(Sq)

    if impl == "banded":
        # Static list of KV-block indices that intersect the band for ANY query.
        blocks = []
        q_lo, q_hi = q_offset, q_offset + Sq - 1
        for j in range(nkb):
            k_lo, k_hi = j * block_k, (j + 1) * block_k - 1
            if causal and k_lo > q_hi:
                continue
            if window > 0 and k_hi < q_lo - window + 1:
                continue
            blocks.append(j)
        block_ids = jnp.array(blocks, dtype=jnp.int32)
        nsteps = len(blocks)
    else:
        block_ids = jnp.arange(nkb, dtype=jnp.int32)
        nsteps = nkb

    def step(carry, j):
        m, l, acc = carry
        kj = jax.lax.dynamic_slice_in_dim(k, j * block_k, block_k, axis=1)
        vj = jax.lax.dynamic_slice_in_dim(v, j * block_k, block_k, axis=1)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qq, kj.astype(jnp.float32))
        # constrain the score block (and thereby its cotangent in the
        # transposed backward scan) to stay batch-sharded — see carry note
        s = shard(s, "batch", "kv_heads", None, None, None)
        k_pos = j * block_k + jnp.arange(block_k)
        bias = _mask_bias(q_pos, k_pos, causal, window)                 # (Sq, bk)
        if kv_len is not None:
            valid = k_pos[None, :] < kv_len[:, None]                    # (B, bk)
            bias = bias[None, None, None] + \
                jnp.where(valid, 0.0, NEG_INF)[:, None, None, None, :]
            s = s + bias
        else:
            s = s + bias[None, None, None]
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + \
            jnp.einsum("bkgqs,bskd->bkgqd", p, vj.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    # The online-softmax carry MUST be explicitly batch-sharded: an unsharded
    # scan carry makes GSPMD replicate it, which all-gathers every f32 score
    # block across the batch axis (measured 825 GB/device/step on the whisper
    # train cell before this constraint — EXPERIMENTS.md §Perf).
    def _c(x):
        return shard(x, "batch", "kv_heads", *([None] * (x.ndim - 2)))

    m0 = _c(jnp.full((B, KV, G, Sq), NEG_INF, dtype=jnp.float32))
    l0 = _c(jnp.zeros((B, KV, G, Sq), dtype=jnp.float32))
    a0 = _c(jnp.zeros((B, KV, G, Sq, Dv), dtype=jnp.float32))

    def step_sharded(carry, j):
        (m, l, acc), ys = step(carry, j)
        return (_c(m), _c(l), _c(acc)), ys

    (m, l, acc), _ = maybe_scan(step_sharded, (m0, l0, a0), block_ids,
                                length=nsteps)
    o = acc / jnp.maximum(l, 1e-30)[..., None]                # (B,KV,G,Sq,Dv)
    o = jnp.moveaxis(o, 3, 1).reshape(B, Sq, H, Dv)
    return o.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, kv_len, *, window=0):
    """Single-token decode. q:(B,1,H,D); caches:(B,Smax,KV,D); kv_len:(B,).

    Attends to positions < kv_len (per sequence); with a window only the last
    ``window`` positions are valid. O(Smax) per step.
    """
    B, _, H, D = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    qq = q.reshape(B, KV, G, D).astype(jnp.float32) / jnp.sqrt(D).astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", qq, k_cache.astype(jnp.float32))
    pos = jnp.arange(k_cache.shape[1])
    valid = pos[None, :] < kv_len[:, None]
    if window > 0:
        valid &= pos[None, :] >= (kv_len[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, D).astype(q.dtype)
