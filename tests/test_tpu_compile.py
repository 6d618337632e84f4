"""Compile rehearsal for one TPU v5e chip, with no chip attached.

The TPU compiler is installed alongside jax, so the Pallas kernels of the
main path and the full-width Qwen3-0.6B decode step are compiled here for a
*described* v5e:2x2 topology (one of its chips). Nothing runs: these tests
catch what interpret mode cannot — block shapes the TPU lowering refuses,
ops the chip has no instruction for, programs that overflow HBM.

The topology is described only inside the ``topo`` fixture, never while a
module is imported: each pytest-xdist worker imports every test file, and
only one process may hold the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.act_clip import act_clip_count
from repro.kernels.block_sparse_matmul import (block_sparse_matmul,
                                               build_tile_schedule)
from repro.models import build_model
from repro.serve.serve_loop import ServeSession

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # else the compiler logs to /tmp
        from jax.experimental import topologies
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:                  # no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without the chip; keep it out of any cache
        old = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        yield t
        jax.config.update("jax_enable_compilation_cache", old)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("K,N", [(1024, 3072), (3072, 1024)])
def test_block_sparse_matmul_compiles_for_v5e(one_chip, K, N):
    """Qwen3-0.6B FFN widths (up and down projections), M = 128, bf16."""
    M = 128
    mask = np.random.default_rng(0).random((K // 128, N // 128)) < 0.5
    counts, indices = build_tile_schedule(mask)
    args = (_spec((M, K), jnp.bfloat16, one_chip),
            _spec((K, N), jnp.bfloat16, one_chip),
            _spec(counts.shape, jnp.int32, one_chip),
            _spec(indices.shape, jnp.int32, one_chip))

    def f(x, w, c, i):
        return block_sparse_matmul(x, w, c, i, interpret=False)

    compiled = jax.jit(f).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape,dtype", [((512, 256), jnp.bfloat16),
                                         ((1024, 1024), jnp.float32)])
def test_act_clip_count_compiles_for_v5e(one_chip, shape, dtype):
    """Multi-tile grids with 256x256 blocks (a per-tile (1, 1) count block
    in SMEM was refused by the TPU lowering here)."""
    def f(x, tau):
        return act_clip_count(x, tau, bm=256, bn=256, interpret=False)

    compiled = jax.jit(f).lower(_spec(shape, dtype, one_chip),
                                _spec((), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out = jax.eval_shape(f, jax.ShapeDtypeStruct(shape, dtype),
                         jax.ShapeDtypeStruct((), jnp.float32))
    assert out[1].shape == (shape[0] // 256, shape[1] // 256)


def _decode_args(api, B, S_max, sharding):
    """Shapes of (params, cache, token) of one decode step, on the chip."""
    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: _spec(a.shape, a.dtype, sharding), tree)

    params = on_chip(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: api.init_cache(B, S_max)))
    return params, cache, _spec((B, 1), jnp.int32, sharding)


def test_qwen3_decode_step_fits_v5e_hbm(one_chip):
    """The full-width Qwen3-0.6B decode step (B=8, S_max=1024) compiles
    for one v5e chip and its program fits the chip's 16 GiB of HBM."""
    api = build_model(get_config("qwen3-0.6b"))
    args = _decode_args(api, 8, 1024, one_chip)
    compiled = jax.jit(api.decode_step).lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, total


def test_served_qwen3_decode_step_writes_its_cache_in_place(one_chip):
    """The decode step as ``ServeSession`` jits it (the cache donated), at
    the serve benchmark's shape (B=32, S_max=768): the output cache aliases
    the donated input, no instruction copies the whole K or V buffer, and
    no ``select`` rewrites a layer's whole (B, S, KV, hd) slice; each layer
    writes only its B new rows."""
    cfg = get_config("qwen3-0.6b")
    api = build_model(cfg)
    B, S_max = 32, 768
    params, cache, token = _decode_args(api, B, S_max, one_chip)
    sess = ServeSession(api, None, batch_slots=B, S_max=S_max)
    compiled = sess._decode.lower(params, cache, token).compile()

    kv_bytes = sum(cache[n].size * cache[n].dtype.itemsize for n in "kv")
    assert compiled.memory_analysis().alias_size_in_bytes >= kv_bytes

    def producers(shape):
        """Opcodes of the instructions that produce a bf16 ``shape``."""
        dims = ",".join(map(str, shape))
        return re.findall(rf"= bf16\[{dims}\]\{{[^}}]*\}} ([\w-]+)\(",
                          compiled.as_text())

    full = cache["k"].shape
    assert full == (cfg.num_layers, B, S_max, cfg.num_kv_heads,
                    cfg.resolved_head_dim)
    assert "scatter" in producers(full)
    assert not [op for op in producers(full) if op.startswith("copy")]
    assert "select" not in producers(full[1:])
