"""Pipeline parallelism (GPipe-style) via shard_map + collective_permute.

The paper's architecture IS a layer pipeline (Fig. 3); on a TPU mesh the
equivalent is stage parallelism: layers are partitioned into S stages mapped
to a 'stage' mesh axis, microbatches flow stage-to-stage over ICI with
``jax.lax.ppermute``, and the bubble fraction is (S-1)/(S-1+M) for M
microbatches. The HASS DSE's rate balancing (Eq. 4-5) chooses the layer->
stage assignment so per-stage (sparsity-scaled) work is even — exported here
as ``balanced_stage_assignment``.

Stages run the *same* scanned-block program with their own parameter shard —
layer-stacked params make a stage just a contiguous slice of the stack.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.perf_model import LayerCost


def balanced_stage_assignment(costs: Sequence[float], n_stages: int
                              ) -> List[int]:
    """Contiguous partition of layers into stages minimizing the max stage
    cost (the pipeline bottleneck, Eq. 3). DP over prefix sums; costs are the
    sparsity-scaled per-layer times from the HASS perf model."""
    L = len(costs)
    n_stages = min(n_stages, L)
    pre = np.concatenate([[0.0], np.cumsum(costs)])

    def seg(i, j):
        return pre[j] - pre[i]

    dp = np.full((n_stages + 1, L + 1), np.inf)
    cut = np.zeros((n_stages + 1, L + 1), dtype=int)
    dp[0, 0] = 0.0
    for s in range(1, n_stages + 1):
        for j in range(1, L + 1):
            for i in range(s - 1, j):
                v = max(dp[s - 1, i], seg(i, j))
                if v < dp[s, j]:
                    dp[s, j], cut[s, j] = v, i
    bounds = [L]
    for s in range(n_stages, 0, -1):
        bounds.append(int(cut[s, bounds[-1]]))
    bounds = bounds[::-1]
    assign = []
    for s in range(n_stages):
        assign += [s] * (bounds[s + 1] - bounds[s])
    return assign


def make_pipelined_fn(stage_fn: Callable, mesh: Mesh, *, n_stages: int,
                      n_microbatches: int, stage_axis: str = "stage"):
    """Wrap ``stage_fn(stage_params, x) -> x`` into a GPipe loop.

    stage_params: leading axis = stage (sharded over stage_axis).
    x: (n_microbatches, mb, ...) replicated; returns same shape.
    Schedule: T = n_microbatches + n_stages - 1 ticks; at tick t, stage s
    processes microbatch t - s; activations hop s -> s+1 via ppermute.
    """
    S, M = n_stages, n_microbatches

    def pipelined(stage_params, x):
        def body(params_local, xs):
            params_local = jax.tree_util.tree_map(lambda a: a[0], params_local)
            sid = jax.lax.axis_index(stage_axis)
            state = jnp.zeros_like(xs[0])                  # stage input buffer
            outs = jnp.zeros_like(xs)

            def tick(carry, t):
                state, outs = carry
                mb_idx = t - sid
                feed = jnp.where(sid == 0,
                                 xs[jnp.clip(t, 0, M - 1)], state)
                y = stage_fn(params_local, feed)
                valid = (mb_idx >= 0) & (mb_idx < M)
                # last stage writes its result at mb_idx
                outs = jax.lax.cond(
                    valid & (sid == S - 1),
                    lambda o: o.at[jnp.clip(mb_idx, 0, M - 1)].set(y),
                    lambda o: o, outs)
                # hop to next stage (ring; last->first carries garbage, unused)
                nxt = jax.lax.ppermute(
                    y, stage_axis, [(i, (i + 1) % S) for i in range(S)])
                return (nxt, outs), None

            (_, outs), _ = jax.lax.scan(tick, (state, outs),
                                        jnp.arange(S + M - 1))
            return outs[None]                    # (1, M, mb, ...) per stage

        specs_p = jax.tree_util.tree_map(
            lambda _: P(stage_axis), stage_params)
        stacked = jax.shard_map(body, mesh=mesh,
                                in_specs=(specs_p, P()),
                                out_specs=P(stage_axis),
                                check_vma=False)(stage_params, x)
        return stacked[-1]                       # the last stage's outputs

    return pipelined


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_stages - 1 + n_microbatches)
