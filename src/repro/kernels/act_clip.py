"""Fused activation clip + zero-count Pallas kernel — the SPE clip unit.

One VMEM pass produces (a) the clipped activations (|x| < tau -> 0, the
dynamic activation sparsity of §III) and (b) per-tile zero counts, which feed
the calibration statistics that drive both the perf model (S_a in Eq. 1) and
the buffer-sizing heuristic — on hardware this is the "dedicated counter"
next to the arbiter in Fig. 3.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# the per-tile count is written as one native (8, 128) int32 VMEM tile: a
# (1, 1) block per grid step is refused by the TPU lowering (block dims must
# be (8, 128)-divisible or span the array), and a lane-dense block keeps the
# grid axes "parallel"
_CNT_TILE = (8, 128)


def _kernel(x_ref, tau_ref, y_ref, cnt_ref):
    # compare in f32: v5e has no bf16 vector compare, and the upcast is exact
    x = x_ref[...].astype(jnp.float32)
    tau = tau_ref[0, 0]
    y = jnp.where(jnp.abs(x) >= tau, x, 0.0)
    y_ref[...] = y.astype(y_ref.dtype)
    cnt_ref[...] = jnp.full(_CNT_TILE, jnp.sum(y == 0.0), jnp.int32)


def act_clip_count(x: jnp.ndarray, tau, *, bm: int = 256, bn: int = 256,
                   interpret: bool = False):
    """x: (M, N) -> (clipped (M, N), zero count per (bm, bn) tile).

    M, N must be multiples of the block sizes (``ops.act_clip`` pads).
    """
    M, N = x.shape
    assert M % bm == 0 and N % bn == 0, (x.shape, bm, bn)
    grid = (M // bm, N // bn)
    tau_arr = jnp.full((1, 1), tau, dtype=jnp.float32)

    y, cnt = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((None, None) + _CNT_TILE,
                         lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), x.dtype),
            jax.ShapeDtypeStruct(grid + _CNT_TILE, jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x, tau_arr)
    return y, cnt[:, :, 0, 0]
