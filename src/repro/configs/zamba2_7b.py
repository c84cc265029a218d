"""Zamba2-7B: 81 Mamba2 layers; before each of the 13 hybrid layers one of
two shared transformer blocks (used alternately) reads concat(h, e0), and its
output, through a per-layer adapter and linear, is added to that Mamba2
layer's input [arXiv:2411.15242; hf:Zyphra/Zamba2-7B-Instruct config.json]."""
from .base import ModelConfig, register

HYBRID_LAYER_IDS = (6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77)

CONFIG = register(ModelConfig(
    name="zamba2-7b", family="hybrid",
    n_layers=81, d_model=3584, n_heads=32, n_kv_heads=32, head_dim=224,
    d_ff=14336, vocab=32000, tie_embeddings=True, rope_theta=10000.0,
    attn_in=7168, attn_scale=(224 / 2) ** -0.5, mlp_act="gelu",
    ssm_state=64, ssm_expand=2, ssm_headdim=64, ssm_ngroups=2, ssm_chunk=256,
    hybrid_layer_ids=HYBRID_LAYER_IDS, n_shared_blocks=2, adapter_rank=128,
    norm_eps=1e-5, source="arXiv:2411.15242",
))

SMOKE = ModelConfig(
    name="zamba2-smoke", family="hybrid",
    n_layers=6, d_model=64, n_heads=4, n_kv_heads=4, head_dim=32,
    d_ff=160, vocab=256, tie_embeddings=True,
    attn_in=128, attn_scale=(32 / 2) ** -0.5, mlp_act="gelu",
    ssm_state=16, ssm_expand=2, ssm_headdim=16, ssm_ngroups=2, ssm_chunk=8,
    hybrid_layer_ids=(2, 4), n_shared_blocks=2, adapter_rank=8,
    source="smoke",
)
