"""Jit'd public wrappers around the Pallas kernels.

Handle padding to block multiples, schedule construction from pruned weights,
and backend selection (``interpret=True`` executes the kernel bodies in
Python on CPU — the validation mode of the CPU tests; on a real TPU
``interpret=False`` compiles via Mosaic).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.act_clip import act_clip_count
from repro.kernels.block_sparse_matmul import (block_sparse_matmul,
                                               build_tile_schedule)


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _pad_to(x: jnp.ndarray, m0: int, m1: int) -> jnp.ndarray:
    p0, p1 = (-x.shape[0]) % m0, (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def weight_tile_mask(w: np.ndarray, bk: int = 128, bn: int = 128) -> np.ndarray:
    """(Kt, Nt) bool: which (bk, bn) tiles of a pruned weight are non-zero."""
    w = np.asarray(w)
    K, N = w.shape
    wp = np.pad(w, ((0, (-K) % bk), (0, (-N) % bn)))
    t = wp.reshape(wp.shape[0] // bk, bk, wp.shape[1] // bn, bn)
    return np.any(t != 0, axis=(1, 3))


class SparseWeight:
    """A pruned weight packaged with its static tile schedule (the paper's
    compile-time arbiter table). Build once after pruning, reuse per step."""

    def __init__(self, w, bk: int = 128, bn: int = 128):
        self.bk, self.bn = bk, bn
        self.shape = tuple(w.shape)
        mask = weight_tile_mask(np.asarray(w), bk, bn)
        counts, indices = build_tile_schedule(mask)
        self.mask = jnp.asarray(mask)
        self.counts = jnp.asarray(counts)
        self.indices = jnp.asarray(indices)
        self.w_padded = _pad_to(jnp.asarray(w), bk, bn)
        self.tile_density = float(mask.mean())

    def matmul(self, x: jnp.ndarray, *, bm: int = 128,
               interpret: Optional[bool] = None) -> jnp.ndarray:
        """x: (M, K) -> (M, N) f32, skipping all-zero weight tiles."""
        M, K = x.shape
        xp = _pad_to(x, bm, self.bk)
        out = block_sparse_matmul(xp, self.w_padded, self.counts, self.indices,
                                  bm=bm, bk=self.bk, bn=self.bn,
                                  interpret=_auto_interpret(interpret))
        return out[:M, :self.shape[1]]


def block_sparse_dense(x, w, *, bm=128, bk=128, bn=128, interpret=None):
    """One-shot convenience: build schedule from w's zeros and multiply."""
    return SparseWeight(w, bk, bn).matmul(x, bm=bm, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def _act_clip_jit(x2d, tau, bm, bn, interpret):
    return act_clip_count(x2d, tau, bm=bm, bn=bn, interpret=interpret)


def act_clip(x: jnp.ndarray, tau, *, bm: int = 256, bn: int = 256,
             interpret: Optional[bool] = None):
    """Clip |x| < tau to 0; returns (y, total zero count). Any shape."""
    shape = x.shape
    n = x.size
    cols = min(n, bn)
    x2 = x.reshape(-1, cols) if n % cols == 0 else \
        jnp.pad(x.reshape(-1), (0, (-n) % cols)).reshape(-1, cols)
    rows = x2.shape[0]
    bm_eff = min(bm, rows)
    x2 = _pad_to(x2, bm_eff, cols)
    y, cnt = _act_clip_jit(x2, jnp.float32(tau), bm_eff, cols,
                           _auto_interpret(interpret))
    pad_zeros = y.size - n          # padding contributes zeros to the count
    y = y.reshape(-1)[:n].reshape(shape)
    return y, cnt.sum() - pad_zeros
