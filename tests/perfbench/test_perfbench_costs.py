"""The benchmark's yardstick: peaks by device kind, and the operation and
byte counts, checked against the program's own shapes."""
import json
import os

import pytest

import cells
from perfbench import costs

ROOT = cells.ROOT


def _cfg(name):
    with open(os.path.join(ROOT, "perfbench", "configs", name)) as f:
        return json.load(f)


def test_peaks_of_a_v5e():
    p = costs.load_peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_an_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        costs.load_peaks("TPU v4")
    with pytest.raises(KeyError):
        costs.load_peaks("cpu")


def test_resnet18_table_is_the_program_network():
    from repro.configs.paper_cnns import RESNET18
    from repro.models import cnn
    cfg = _cfg("resnet18.json")
    specs = cnn.build_specs(RESNET18)
    assert [l["name"] for l in cfg["layers"]] == [s.name for s in specs]
    for l, s in zip(cfg["layers"], specs):
        assert l["kind"] == s.kind
        assert costs.cnn_layer_macs(l) == s.macs
        for key in ("cin", "cout", "k", "stride", "in_hw", "out_hw"):
            if key in l:
                assert l[key] == getattr(s, key), (l["name"], key)
        assert l.get("input") == s.input_from
        assert l.get("residual") == s.residual_from
    rows = costs.cnn_prunable(cfg)
    assert [l["name"] for l in rows] == [s.name for s in specs if s.prunable]
    assert len(rows) == 21
    gmac = sum(costs.cnn_layer_macs(l) for l in rows) / 1e9
    assert gmac == pytest.approx(1.814, abs=1e-3)


def test_kept_flops_scale_with_the_kept_weights():
    cfg = _cfg("resnet18.json")
    n = len(costs.cnn_prunable(cfg))
    dense = costs.cnn_kept_flops(cfg, [0.0] * n, 32)
    assert dense == pytest.approx(2 * 1.814073344e9 * 32)
    assert costs.cnn_kept_flops(cfg, [0.5] * n, 32) == pytest.approx(
        dense / 2)
    with pytest.raises(ValueError):
        costs.cnn_kept_flops(cfg, [0.0] * (n - 1), 32)


def test_qwen3_counts_match_the_program_parameters():
    import jax
    from repro.configs import get_config
    from repro.models import build_model
    cfg = _cfg("qwen3-0.6b.json")
    assert costs.lm_non_embedding_params(cfg) == 440_467_456
    assert costs.kv_bytes_per_token(cfg) == 114_688
    shapes = jax.eval_shape(build_model(get_config("qwen3-0.6b")).init,
                            jax.random.PRNGKey(0))
    sizes = {"/".join(str(k.key) for k in path): leaf.size
             for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    non_embed = sum(v for k, v in sizes.items() if k != "embed")
    assert non_embed == costs.lm_non_embedding_params(cfg)
    assert sizes["embed"] == costs.lm_embedding_params(cfg)


def test_qwen3_flops_and_bytes():
    cfg = _cfg("qwen3-0.6b.json")
    per_tok = costs.lm_matmul_flops_per_token(cfg)
    assert per_tok == 2 * (costs.lm_non_embedding_params(cfg)
                           - 28 * (2 * 128 + 2 * 1024) - 1024)
    # attention: 4 L H hd per attended key
    assert costs.lm_attention_flops(cfg, 10) == 4 * 28 * 16 * 128 * 10
    assert costs.decode_flops(cfg, 1) == per_tok + \
        costs.lm_attention_flops(cfg, 1) + 2 * 1024 * 151936
    p = costs.prefill_flops(cfg, 4)
    assert p == 4 * per_tok + costs.lm_attention_flops(cfg, 1 + 2 + 3 + 4) \
        + costs.lm_head_flops(cfg)
    assert costs.request_flops(cfg, 4, 1) == p
    w = costs.weight_bytes_bf16(cfg)
    assert w == pytest.approx(1.19e9, rel=0.01)
    assert costs.decode_step_min_bytes(cfg, 100) == w + 100 * 114_688
