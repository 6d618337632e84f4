"""``lm_layer_costs`` invariants across all ten assigned architectures
(DESIGN.md §11: per-token workloads, sample = token, attn non-prunable).
"""
import math

import pytest

from repro.configs import ASSIGNED, get_config
from repro.core.perf_model import (MXU_TILE, lm_block_bounds, lm_layer_costs,
                                   param_count, thin_cut_points,
                                   tile_quantize_sparsity)

ARCHS = sorted(ASSIGNED)


@pytest.fixture(scope="module")
def stacks():
    return {a: lm_layer_costs(get_config(a)) for a in ARCHS}


@pytest.mark.parametrize("arch", ARCHS)
def test_positive_workloads(arch, stacks):
    for l in stacks[arch]:
        assert l.macs > 0, l.name
        assert l.m_dot > 0, l.name
        assert l.act_in > 0 and l.act_out > 0, l.name


@pytest.mark.parametrize("arch", ARCHS)
def test_linear_weight_counts(arch, stacks):
    """Linears: macs = cin*cout*n_apply with cin = m_dot, weight_count =
    cin*cout, act_in = cin*n_apply, act_out = cout*n_apply. Hence
    macs == m_dot * act_out and weight_count * n_apply == macs."""
    for l in stacks[arch]:
        if l.kind != "linear":
            continue
        assert l.macs == l.m_dot * l.act_out, l.name
        n_apply = l.act_in // l.m_dot
        assert l.act_in == l.m_dot * n_apply, l.name
        assert l.weight_count * n_apply == l.macs, l.name


@pytest.mark.parametrize("arch", ARCHS)
def test_attn_layers_not_prunable(arch, stacks):
    """Attention score/value products are data-data: no weight to prune."""
    attn = [l for l in stacks[arch] if l.kind == "attn"]
    assert len(attn) == get_config(arch).num_layers
    for l in attn:
        assert not l.prunable and l.weight_count == 0, l.name


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if get_config(a).moe is not None])
def test_moe_active_expert_multiplier(arch, stacks):
    """MoE FFN matmuls are applied once per *active* expert
    (top_k + shared); the per-token MAC count carries that multiplier."""
    cfg = get_config(arch)
    active = cfg.moe.top_k + cfg.moe.num_shared_experts
    fe = cfg.moe.expert_d_ff or cfg.d_ff
    for l in stacks[arch]:
        if l.name.endswith(".moe_up"):
            assert l.macs == cfg.d_model * fe * active, l.name
            assert l.act_in == cfg.d_model * active, l.name
            assert l.weight_count == cfg.d_model * fe, l.name


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if get_config(a).hybrid_layer_ids])
def test_hybrid_shared_layers_at_cadence(arch, stacks):
    """Hybrid (zamba-style) stacks price the shared block before each ssm
    layer of ``hybrid_layer_ids`` and nowhere else."""
    cfg = get_config(arch)
    expect = set(cfg.hybrid_layer_ids)
    got = {int(l.name.split(".")[0][1:]) for l in stacks[arch]
           if ".shared_" in l.name}
    assert got == expect
    n_shared = sum(1 for l in stacks[arch] if ".shared_" in l.name)
    assert n_shared == 2 * len(expect)       # shared_qkvo + shared_ffn


@pytest.mark.parametrize("arch", ARCHS)
def test_param_count_consistency(arch, stacks):
    """``param_count`` == stack weights + embedding + inactive experts,
    recomputed here from first principles."""
    cfg = get_config(arch)
    total = sum(l.weight_count for l in stacks[arch])
    total += cfg.vocab_size * cfg.d_model
    if cfg.moe is not None:
        fe = cfg.moe.expert_d_ff or cfg.d_ff
        inactive = cfg.moe.num_experts - cfg.moe.top_k
        total += cfg.num_layers * inactive * 3 * cfg.d_model * fe
    assert param_count(cfg) == total


@pytest.mark.parametrize("arch", ARCHS)
def test_block_bounds_partition_the_stack(arch, stacks):
    """One block per transformer layer plus the unembed tail; boundaries
    strictly increasing in 1..L-1 (valid DP cut candidates)."""
    layers = stacks[arch]
    bounds = lm_block_bounds(layers)
    assert bounds == sorted(set(bounds))
    assert all(1 <= b <= len(layers) - 1 for b in bounds)
    assert len(bounds) + 1 == get_config(arch).num_layers + 1
    # every boundary starts a new name prefix
    for b in bounds:
        assert layers[b].name.split(".")[0] != \
            layers[b - 1].name.split(".")[0]


def test_seq_len_scales_attention_only():
    cfg = get_config("qwen3-0.6b")
    short = {l.name: l.macs for l in lm_layer_costs(cfg, seq_len=1)}
    long = {l.name: l.macs for l in lm_layer_costs(cfg, seq_len=4096)}
    for name in short:
        if name.endswith(".attn"):
            assert long[name] > short[name]
        else:
            assert long[name] == short[name]


def test_sliding_window_caps_attention_macs():
    """mixtral's SWA bounds per-token attention work at the window size."""
    cfg = get_config("mixtral-8x7b")
    assert cfg.attn_window == 4096
    at_win = [l.macs for l in lm_layer_costs(cfg, seq_len=4096)
              if l.kind == "attn"]
    beyond = [l.macs for l in lm_layer_costs(cfg, seq_len=32768)
              if l.kind == "attn"]
    assert beyond == at_win


def test_thin_cut_points():
    bounds = list(range(10, 200, 10))
    kept = thin_cut_points(bounds, 5)
    assert len(kept) == 5
    assert set(kept) <= set(bounds)
    assert kept == sorted(kept)
    assert kept[0] == bounds[0] and kept[-1] == bounds[-1]
    assert thin_cut_points(bounds, 0) == bounds
    assert thin_cut_points(bounds, len(bounds) + 5) == bounds


def test_tile_quantize_sparsity():
    # 7168x1536 weights: 56*12 tiles -> steps of 1/672
    n_tiles = math.ceil(7168 / MXU_TILE) * math.ceil(1536 / MXU_TILE)
    q = tile_quantize_sparsity(0.37, 7168, 7168 * 1536)
    assert q <= 0.37 and 0.37 - q < 1.0 / n_tiles
    assert q == math.floor(0.37 * n_tiles) / n_tiles
    # a single tile can only be fully kept or fully pruned
    assert tile_quantize_sparsity(0.9, 64, 64 * 64) == 0.0
    assert tile_quantize_sparsity(1.0, 64, 64 * 64) == 1.0
    assert tile_quantize_sparsity(0.5, 0, 0) == 0.0
