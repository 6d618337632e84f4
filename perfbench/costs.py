"""Operations and bytes a model needs, counted from the configuration files.

These are the benchmark's own yardstick: they read only ``configs/*.json``
and never the program, so a change to the program cannot change what a
utilization or roofline share is measured against.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
BF16_BYTES = 2


def load_peaks(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind``. A kind that the table does
    not list is an error: a utilization against a guessed peak means
    nothing."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"perfbench/peaks.json lists {sorted(table)}")
    return table[device_kind]


# ----------------------------------------------------------------- CNN
def cnn_layer_macs(layer: dict) -> int:
    """Dense multiply-accumulates per image of one row of a CNN table."""
    if layer["kind"] == "conv":
        return (layer["cout"] * layer["cin"] * layer["k"] ** 2
                * layer["out_hw"] ** 2)
    if layer["kind"] == "linear":
        return layer["cin"] * layer["cout"]
    return 0


def cnn_prunable(cfg: dict) -> list:
    """The rows whose weights the search prunes: every conv and linear."""
    return [l for l in cfg["layers"] if l["kind"] in ("conv", "linear")]


def cnn_kept_flops(cfg: dict, weight_sparsity, images: int) -> float:
    """FLOPs of the weights a proposal keeps: each prunable layer's dense
    FLOPs times (1 - its requested weight sparsity), over ``images``."""
    rows = cnn_prunable(cfg)
    if len(weight_sparsity) != len(rows):
        raise ValueError(f"{len(weight_sparsity)} sparsities for "
                         f"{len(rows)} prunable layers")
    return images * sum(2.0 * cnn_layer_macs(l) * (1.0 - float(s))
                        for l, s in zip(rows, weight_sparsity))


# ------------------------------------------------------------------ LM
def lm_dims(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "f": cfg["intermediate_size"],
            "L": cfg["num_hidden_layers"], "H": cfg["num_attention_heads"],
            "KV": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "V": cfg["vocab_size"]}


def lm_layer_params(cfg: dict) -> int:
    """Parameters of one decoder block: q/k/v/o projections, q and k norms,
    the two block norms and the SwiGLU feed-forward."""
    m = lm_dims(cfg)
    d, hd = m["d"], m["hd"]
    attn = d * m["H"] * hd + 2 * d * m["KV"] * hd + m["H"] * hd * d
    return attn + 2 * hd + 2 * d + 3 * d * m["f"]


def lm_non_embedding_params(cfg: dict) -> int:
    m = lm_dims(cfg)
    return m["L"] * lm_layer_params(cfg) + m["d"]     # + final norm


def lm_embedding_params(cfg: dict) -> int:
    m = lm_dims(cfg)
    n = m["V"] * m["d"]
    return n if cfg.get("tie_word_embeddings") else 2 * n


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = BF16_BYTES) -> int:
    """Bytes of K and V that one cached position holds over all layers."""
    m = lm_dims(cfg)
    return m["L"] * 2 * m["KV"] * m["hd"] * bytes_per_value


def lm_matmul_flops_per_token(cfg: dict) -> int:
    """Projection and feed-forward FLOPs of one token through every block."""
    m = lm_dims(cfg)
    d, hd = m["d"], m["hd"]
    per_layer = d * m["H"] * hd + 2 * d * m["KV"] * hd + m["H"] * hd * d \
        + 3 * d * m["f"]
    return 2 * m["L"] * per_layer


def lm_attention_flops(cfg: dict, context: int) -> int:
    """Score and value FLOPs of one query that attends ``context`` keys,
    over every layer and head."""
    m = lm_dims(cfg)
    return 2 * 2 * m["L"] * m["H"] * m["hd"] * context


def lm_head_flops(cfg: dict) -> int:
    m = lm_dims(cfg)
    return 2 * m["d"] * m["V"]


def prefill_flops(cfg: dict, prompt_len: int) -> int:
    """One causal prompt of ``prompt_len`` tokens, logits at its last
    position only."""
    attn = sum(lm_attention_flops(cfg, t + 1) for t in range(prompt_len))
    return prompt_len * lm_matmul_flops_per_token(cfg) + attn \
        + lm_head_flops(cfg)


def decode_flops(cfg: dict, context: int) -> int:
    """One decode step of one sequence whose new token sees ``context``
    keys (itself included), logits included."""
    return lm_matmul_flops_per_token(cfg) + lm_attention_flops(cfg, context) \
        + lm_head_flops(cfg)


def request_flops(cfg: dict, prompt_len: int, max_new: int) -> int:
    """A request served by prefill and then ``max_new - 1`` decode steps:
    the last token is sampled but never fed back."""
    return prefill_flops(cfg, prompt_len) + sum(
        decode_flops(cfg, prompt_len + j + 1) for j in range(max_new - 1))


def weight_bytes_bf16(cfg: dict) -> int:
    """Every weight once in bf16: what a decode step has to stream at
    least (the tied embedding is read whole as the LM head)."""
    return BF16_BYTES * (lm_non_embedding_params(cfg)
                         + lm_embedding_params(cfg))


def decode_step_min_bytes(cfg: dict, live_tokens: int) -> int:
    """Least bytes of one decode step over a batch whose caches hold
    ``live_tokens`` positions in all: the weights once plus the live KV."""
    return weight_bytes_bf16(cfg) + live_tokens * kv_bytes_per_token(cfg)
