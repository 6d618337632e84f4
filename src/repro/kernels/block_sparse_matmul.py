"""Block-sparse matmul Pallas TPU kernel — the MXU adaptation of the SPE.

The paper's SPE keeps every MAC busy by statically scheduling only non-zero
(weight, activation) pairs (arbiter + zero-filter, Fig. 3). A systolic MXU
cannot skip individual MACs, so the TPU-native equivalent operates at VMEM
tile granularity: weight sparsity is compile-time known, so for every output
tile column we *precompute the list of non-zero K-tiles* and the grid runs
exactly ``nnz`` steps per output tile — zero tiles are never DMA'd from HBM
nor multiplied. Eq. 1's t(S̄)=ceil((1-S̄)M/N) becomes
``steps = nnz_tiles(column)`` with M/N = K/bk tiles.

The schedule (counts, indices) is the arbiter; scalar-prefetch index maps are
the dispatch. Grid = (M/bm, N/bn, max_nnz); the trailing (sequential) axis
accumulates into the output tile.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _build_tile_schedule_ref(mask: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Reference per-column-loop schedule builder — kept as the equivalence
    oracle for the vectorized path (tests, kernels_bench)."""
    mask = np.asarray(mask, dtype=bool)
    Kt, Nt = mask.shape
    counts = mask.sum(axis=0).astype(np.int32)
    max_nnz = max(1, int(counts.max()) if counts.size else 1)
    indices = np.zeros((Nt, max_nnz), dtype=np.int32)
    for j in range(Nt):
        nz = np.nonzero(mask[:, j])[0]
        indices[j, :len(nz)] = nz
    return counts, indices


def tile_mask(w: np.ndarray, bk: int = 128, bn: int = 128) -> np.ndarray:
    """(K, N) weight -> (Kt, Nt) bool map of tiles with any non-zero entry.

    The bridge from a pruned weight to ``build_tile_schedule``: pattern
    pruning (tile / N:M / hierarchical, DESIGN.md §16) produces element
    zeros; the Pallas kernel skips at VMEM-tile granularity, so only tiles
    that pruning emptied *entirely* shorten the schedule.
    """
    w = np.asarray(w)
    K, N = w.shape
    assert K % bk == 0 and N % bn == 0, (w.shape, bk, bn)
    t = w.reshape(K // bk, bk, N // bn, bn)
    return (t != 0).any(axis=(1, 3))


# schedule memo: a weight is pruned once and multiplied every step, and
# several layers often share one mask shape+pattern (tile-structured
# pruning is deterministic), so schedules are cached per mask content
_SCHEDULE_CACHE: dict = {}
_SCHEDULE_CACHE_MAX = 256


def build_tile_schedule(mask: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """mask: (Kt, Nt) bool -> (counts (Nt,), indices (Nt, max_nnz)) int32.

    indices[j, s] is the K-tile id of the s-th non-zero tile in column j
    (padded with 0 past counts[j]; padded steps are masked in the kernel).
    This is the compile-time static schedule — the paper's arbiter, resolved
    ahead of time because weight sparsity is known at compile time (§III).

    Vectorized: one ``np.nonzero`` over the transposed mask yields every
    (column, K-tile) pair in column-major order, and a cumsum of the
    per-column counts scatters each pair into its step slot — O(nnz) flat
    numpy instead of the reference's per-column Python loop. Results are
    memoized on the mask bytes — rebuilding the schedule for an unchanged
    weight is a dict hit (``kernels_bench.py`` gates both).
    """
    mask = np.asarray(mask, dtype=bool)
    key = (mask.shape, mask.tobytes())
    hit = _SCHEDULE_CACHE.get(key)
    if hit is not None:
        return hit
    Kt, Nt = mask.shape
    if Kt == 0 or Nt == 0:
        return _build_tile_schedule_ref(mask)
    counts = mask.sum(axis=0).astype(np.int32)
    max_nnz = max(1, int(counts.max()) if counts.size else 1)
    flat = np.flatnonzero(np.ascontiguousarray(mask.T))
    cols, rows = np.divmod(flat, Kt)     # column-major: ascending rows
    starts = np.zeros(Nt, dtype=np.int64)     # within each column
    starts[1:] = np.cumsum(counts[:-1])
    slot = np.arange(len(rows), dtype=np.int64) - starts[cols]
    indices = np.zeros((Nt, max_nnz), dtype=np.int32)
    indices[cols, slot] = rows
    if len(_SCHEDULE_CACHE) >= _SCHEDULE_CACHE_MAX:
        _SCHEDULE_CACHE.clear()
    out = (counts, indices)
    _SCHEDULE_CACHE[key] = out
    return out


def _kernel(counts, indices, x_ref, w_ref, o_ref, *, bm, bn):
    i, j, s = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(s < counts[j])
    def _accum():
        o_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                              preferred_element_type=jnp.float32)


def block_sparse_matmul(x: jnp.ndarray, w: jnp.ndarray,
                        counts: jnp.ndarray, indices: jnp.ndarray,
                        *, bm: int = 128, bk: int = 128, bn: int = 128,
                        interpret: bool = False) -> jnp.ndarray:
    """x: (M, K) @ w: (K, N) skipping all-zero weight tiles.

    counts/indices from ``build_tile_schedule``. M, K, N must be multiples of
    the block sizes (``ops.block_sparse_dense`` pads). Returns f32 (M, N).
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2 and M % bm == 0 and K % bk == 0 and N % bn == 0, \
        (x.shape, w.shape, bm, bk, bn)
    Nt = N // bn
    max_nnz = indices.shape[1]
    assert counts.shape == (Nt,) and indices.shape == (Nt, max_nnz)

    grid = (M // bm, Nt, max_nnz)

    def x_map(i, j, s, counts_ref, idx_ref):
        return (i, idx_ref[j, s])

    def w_map(i, j, s, counts_ref, idx_ref):
        return (idx_ref[j, s], j)

    def o_map(i, j, s, counts_ref, idx_ref):
        return (i, j)

    return pl.pallas_call(
        functools.partial(_kernel, bm=bm, bn=bn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), x_map),
                pl.BlockSpec((bk, bn), w_map),
            ],
            out_specs=pl.BlockSpec((bm, bn), o_map),
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(counts, indices, x, w)
