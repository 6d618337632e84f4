"""The Qwen3 family's program side: which registry model serves a
configuration of this family at the file's sizes, and what one served
request costs in FLOPs.

Its plain reference and seeded weights are ``reference/qwen3.py``.
"""
from __future__ import annotations

import dataclasses

from perfbench.costs import request_flops  # noqa: F401  (the dense-GQA count)


def program_config(cfg: dict):
    """The registry's config at the file's sizes."""
    from repro.configs import get_config
    base = get_config(cfg["registry"])
    return dataclasses.replace(
        base, d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        vocab_size=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), act=cfg["hidden_act"],
        tied_embeddings=bool(cfg["tie_word_embeddings"]),
        qkv_bias=bool(cfg["attention_bias"]), dtype=cfg["torch_dtype"])
