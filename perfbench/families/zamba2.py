"""The Zamba2 family's program side: which registry model serves a
configuration of this family at the file's sizes, and what serving it
costs in FLOPs and in bytes.

Counted from the configuration file alone (never from the program), after
transformers' ``modeling_zamba2.py``: per token, every Mamba2 layer's
in_proj and out_proj and its state update, and at each site of
``hybrid_layer_ids`` the shared block used there (q/k/v over the 2 *
hidden concatenation, o, gate_up, down), that site's adapter and
``linear``, and its attention over the context; the LM head only where
logits are produced. The depthwise conv, norms and gates are left out, as
the dense count leaves out norms and activations.

Its plain reference and seeded weights are ``reference/zamba2.py``.
"""
from __future__ import annotations

import dataclasses

BF16, F32 = 2, 4


def program_config(cfg: dict):
    """The registry's config at the file's sizes. Refuses a file that asks
    for what the program does not compute."""
    from repro.configs import SSMConfig, get_config
    unsupported = {"use_shared_attention_adapter": False,
                   "use_mem_rope": True, "use_long_context": False,
                   "add_bias_linear": False, "use_conv_bias": True,
                   "time_step_min": 1e-3, "time_step_limit": None}
    for key, want in unsupported.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"zamba2: {key}={cfg[key]!r} is not served "
                             f"(the program computes {key}={want!r})")
    d = _dims(cfg)
    if cfg["n_mamba_heads"] != d["H"] or \
            cfg["attention_head_dim"] * cfg["num_attention_heads"] != 2 * d["d"]:
        raise ValueError("zamba2: head counts disagree with the widths")
    base = get_config(cfg["registry"])
    return dataclasses.replace(
        base, d_model=d["d"], d_ff=d["f"], num_layers=d["L"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=d["hd"],
        vocab_size=d["V"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), act=cfg["hidden_act"],
        tied_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["torch_dtype"],
        ssm=SSMConfig(state_dim=d["N"], head_dim=d["P"],
                      expand=cfg["mamba_expand"], conv_dim=d["K"],
                      chunk=cfg["chunk_size"], ngroups=d["G"]),
        hybrid_layer_ids=tuple(cfg["hybrid_layer_ids"]),
        num_mem_blocks=d["M"], adapter_rank=d["r"])


def _dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    d_in = cfg["mamba_expand"] * d
    return {"d": d, "d_in": d_in, "H": d_in // cfg["mamba_headdim"],
            "P": cfg["mamba_headdim"], "N": cfg["mamba_d_state"],
            "G": cfg["mamba_ngroups"], "K": cfg["mamba_d_conv"],
            "f": cfg["intermediate_size"], "r": cfg["adapter_rank"],
            "L": cfg["num_hidden_layers"], "M": cfg["num_mem_blocks"],
            "sites": len(cfg["hybrid_layer_ids"]),
            "AH": cfg["num_attention_heads"],
            "AKV": cfg["num_key_value_heads"],
            "hd": cfg["attention_head_dim"], "V": cfg["vocab_size"]}


# --------------------------------------------------------------- params
def _mamba_layer_params(m: dict) -> int:
    conv_ch = m["d_in"] + 2 * m["G"] * m["N"]
    return (m["d"]                                          # ln
            + m["d"] * (m["d_in"] + conv_ch + m["H"])       # in_proj
            + m["K"] * conv_ch + conv_ch                    # conv w, b
            + 3 * m["H"]                                    # A_log, D, dt_bias
            + m["d_in"]                                     # gated norm
            + m["d_in"] * m["d"])                           # out_proj


def _shared_block_params(m: dict) -> int:
    d, hd = m["d"], m["hd"]
    qkv = 2 * d * (m["AH"] + 2 * m["AKV"]) * hd
    return (2 * d + qkv + m["AH"] * hd * d                  # ln1, qkv, o
            + d + 3 * d * m["f"])                           # ln2, gate_up, down


def _site_params(m: dict) -> int:
    return m["r"] * (m["d"] + 2 * m["f"]) + m["d"] * m["d"]  # adapter, linear


def weight_bytes(cfg: dict) -> int:
    """Every weight once in bf16, each shared block once: what a decode
    step has to stream at least (the tied embedding is read whole as the
    LM head)."""
    m = _dims(cfg)
    n = (m["L"] * _mamba_layer_params(m) + m["M"] * _shared_block_params(m)
         + m["sites"] * _site_params(m) + m["d"] + m["V"] * m["d"])
    if not cfg["tie_word_embeddings"]:
        n += m["V"] * m["d"]
    return BF16 * n


def recurrent_state_bytes(cfg: dict, batch: int) -> int:
    """The Mamba2 state of ``batch`` sequences: the float32 SSM state and
    the bf16 conv state (the last K-1 inputs) of every layer."""
    m = _dims(cfg)
    conv_ch = m["d_in"] + 2 * m["G"] * m["N"]
    per_layer = F32 * m["H"] * m["P"] * m["N"] \
        + BF16 * (m["K"] - 1) * conv_ch
    return batch * m["L"] * per_layer


def kv_bytes_per_token(cfg: dict) -> int:
    """Bytes of K and V that one cached position holds over every site."""
    m = _dims(cfg)
    return m["sites"] * 2 * m["AKV"] * m["hd"] * BF16


def decode_step_min_bytes(cfg: dict, live_tokens: int, batch: int) -> int:
    """Least bytes of one decode step of ``batch`` sequences whose caches
    hold ``live_tokens`` positions in all: the weights once, the recurrent
    state read and written once, and the live KV of every site."""
    return weight_bytes(cfg) + 2 * recurrent_state_bytes(cfg, batch) \
        + live_tokens * kv_bytes_per_token(cfg)


# ---------------------------------------------------------------- FLOPs
def _token_flops(cfg: dict) -> int:
    """FLOPs of one token through every layer, attention over the context
    apart: the projections of every Mamba2 layer and its state update
    (decay, input and output products: 5 per state element), and at each
    site the shared block's projections, the adapter and the linear."""
    m = _dims(cfg)
    conv_ch = m["d_in"] + 2 * m["G"] * m["N"]
    mamba = 2 * m["d"] * (m["d_in"] + conv_ch + m["H"]) \
        + 2 * m["d_in"] * m["d"] + 5 * m["d_in"] * m["N"]
    d, hd = m["d"], m["hd"]
    site = 2 * (2 * d * (m["AH"] + 2 * m["AKV"]) * hd + m["AH"] * hd * d
                + 3 * d * m["f"]) + 2 * _site_params(m)
    return m["L"] * mamba + m["sites"] * site


def _attention_flops(cfg: dict, context: int) -> int:
    """Score and value FLOPs of one query over ``context`` keys at every
    site."""
    m = _dims(cfg)
    return 2 * 2 * m["sites"] * m["AH"] * m["hd"] * context


def _head_flops(cfg: dict) -> int:
    m = _dims(cfg)
    return 2 * m["d"] * m["V"]


def decode_flops(cfg: dict, context: int) -> int:
    """One decode step of one sequence whose new token sees ``context``
    keys (itself included), logits included."""
    return _token_flops(cfg) + _attention_flops(cfg, context) \
        + _head_flops(cfg)


def request_flops(cfg: dict, prompt_len: int, max_new: int) -> int:
    """A request served by prefill (logits at its last position only) and
    then ``max_new - 1`` decode steps."""
    prefill = prompt_len * _token_flops(cfg) + sum(
        _attention_flops(cfg, t + 1) for t in range(prompt_len)) \
        + _head_flops(cfg)
    return prefill + sum(decode_flops(cfg, prompt_len + j + 1)
                         for j in range(max_new - 1))
