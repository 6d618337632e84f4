import pytest

from repro.configs import (ASSIGNED, SHAPES, SHAPE_BY_NAME, cell_supported,
                           get_config, list_archs, reduce_config)


def test_registry_has_all_assigned():
    expected = {"deepseek-v3-671b", "mixtral-8x7b", "qwen3-0.6b",
                "stablelm-12b", "qwen2.5-3b", "deepseek-67b", "chameleon-34b",
                "rwkv6-1.6b", "whisper-base", "zamba2-1.2b"}
    assert set(ASSIGNED) == expected
    assert len(list_archs()) >= 15          # + paper CNNs


def test_exact_assigned_dims():
    c = get_config("deepseek-v3-671b")
    assert (c.num_layers, c.d_model, c.num_heads, c.vocab_size) == \
        (61, 7168, 128, 129280)
    assert c.moe.num_experts == 256 and c.moe.top_k == 8
    c = get_config("deepseek-67b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
            c.d_ff, c.vocab_size) == (95, 8192, 64, 8, 22016, 102400)
    c = get_config("mixtral-8x7b")
    assert c.attn_window == 4096 and c.moe.num_experts == 8
    c = get_config("qwen2.5-3b")
    assert c.qkv_bias and c.num_kv_heads == 2
    c = get_config("qwen3-0.6b")
    assert c.qk_norm and c.head_dim == 128
    c = get_config("zamba2-1.2b")
    assert c.ssm.state_dim == 64 and \
        c.hybrid_layer_ids == (0, 6, 12, 18, 24, 30, 36)
    c = get_config("whisper-base")
    assert c.enc_layers == 6 and c.vocab_size == 51865


def test_shapes():
    assert {s.name for s in SHAPES} == \
        {"train_4k", "prefill_32k", "decode_32k", "long_500k"}
    assert SHAPE_BY_NAME["train_4k"].global_batch == 256
    assert SHAPE_BY_NAME["long_500k"].seq_len == 524288


def test_cell_support_matrix():
    """40 cells; long_500k runs only for sub-quadratic archs."""
    runs_long = {a for a in ASSIGNED
                 if cell_supported(get_config(a), SHAPE_BY_NAME["long_500k"])[0]}
    assert runs_long == {"rwkv6-1.6b", "zamba2-1.2b", "mixtral-8x7b"}
    total = sum(1 for a in ASSIGNED for s in SHAPES)
    assert total == 40


@pytest.mark.parametrize("arch", sorted(ASSIGNED))
def test_reduce_config_valid(arch):
    cfg = reduce_config(get_config(arch))
    assert cfg.d_model <= 128 and cfg.vocab_size <= 1024
    assert cfg.family == get_config(arch).family
