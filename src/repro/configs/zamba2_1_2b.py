"""Zamba2-1.2B — Mamba2 backbone + one shared (weight-tied) transformer block.

[arXiv:2411.15242; hf]  38L d_model=2048 32H (kv=32, MHA) d_ff=8192
ssm_state=64 vocab=32000. The shared block runs before the Mamba2 layers
in ``hybrid_layer_ids`` (weight-tied across those sites), its attention
over concat(h, embedding) at 2 * d_model with heads of 128. This entry
keeps the repo's historical sites (every sixth layer from layer 0) rather
than the published ones, and leaves out the published model's per-site
LoRA adapters on the attention's q, k and v
(``use_shared_attention_adapter``); it keeps those on the MLP. No
benchmark cell runs it. Hybrid => runs long_500k; the shared-attention KV
cache is windowed at 4096 for that cell.
"""
from repro.configs.base import ModelConfig, SSMConfig

CONFIG = ModelConfig(
    name="zamba2-1.2b",
    family="hybrid",
    num_layers=38,             # mamba2 layers
    d_model=2048,
    num_heads=32,
    num_kv_heads=32,
    head_dim=128,              # attention over 2 * d_model
    d_ff=8192,
    vocab_size=32000,
    ssm=SSMConfig(state_dim=64, head_dim=64, expand=2, conv_dim=4, chunk=64),
    hybrid_layer_ids=(0, 6, 12, 18, 24, 30, 36),
    num_mem_blocks=1,
    adapter_rank=128,
    act="gelu",
    tied_embeddings=True,
)
