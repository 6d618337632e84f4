"""Zamba2-7B(-Instruct) — 81 Mamba2 layers, 13 of them preceded by one of
two weight-tied transformer blocks used in turn.

[hf:Zyphra/Zamba2-7B-Instruct config.json]  81L d_model=3584, Mamba2 with
112 heads of 64, d_state 64, 2 groups, conv 4, expand 2; shared blocks:
MHA over concat(h, embedding) (7168 wide, 32 heads of 224, rotary
positions, softmax scale (224/2)^-0.5), gated exact-GELU MLP 14336 with a
rank-128 adapter per site, and a 3584x3584 ``linear`` per site whose
output is added to that site's Mamba2 input. vocab=32000, tied head.
"""
from repro.configs.base import ModelConfig, SSMConfig

CONFIG = ModelConfig(
    name="zamba2-7b",
    family="hybrid",
    num_layers=81,             # mamba2 layers
    d_model=3584,
    num_heads=32,
    num_kv_heads=32,
    head_dim=224,              # attention over 2 * d_model
    d_ff=14336,
    vocab_size=32000,
    ssm=SSMConfig(state_dim=64, head_dim=64, expand=2, conv_dim=4, chunk=256,
                  ngroups=2),
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    num_mem_blocks=2,
    adapter_rank=128,
    act="gelu",
    tied_embeddings=True,
)
