"""Zamba2-7B-Instruct [hf:Zyphra/Zamba2-7B-Instruct config.json].

81 Mamba2 layers at hidden 3584 (112 SSD heads of 64, d_state 64, 2
groups, conv 4, chunk 256), vocab 32000.  The 13 layers of
``hybrid_layer_ids`` first run one of two shared attention+MLP blocks,
alternating: 32 MHA heads of 224 over concat(hidden, embedding) (7168
wide, scale (224/2)^-0.5, RoPE theta 10000), MLP 14336 with GELU, and a
rank-128 LoRA of the layer's own on the MLP's gate/up projection
(``use_shared_attention_adapter`` false).  Tied embeddings are assumed:
the catalog's config drops the key.  Sub-quadratic.

``SMOKE`` keeps the structure at a CPU size: 6 layers with hybrid layers
2 and 4 on the two blocks, 2 SSD groups, heads of 24, adapter rank 4.
"""
from repro.configs.base import ArchConfig, SSMConfig

CONFIG = ArchConfig(
    name="zamba2-7b",
    family="hybrid",
    n_layers=81,
    d_model=3584,
    n_heads=32,
    n_kv=32,
    d_ff=14_336,
    vocab=32_000,
    d_head=224,
    query_scale=(224 / 2) ** -0.5,
    gate_act="gelu",
    norm_eps=1e-5,
    tie_embeddings=True,
    ssm=SSMConfig(state=64, head_dim=64, expand=2, n_groups=2, conv_kernel=4, chunk=256),
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    num_mem_blocks=2,
    adapter_rank=128,
    subquadratic=True,
)

SMOKE = ArchConfig(
    name="zamba2-7b-smoke",
    family="hybrid",
    n_layers=6,
    d_model=48,
    n_heads=4,
    n_kv=4,
    d_ff=96,
    vocab=256,
    d_head=24,
    query_scale=(24 / 2) ** -0.5,
    gate_act="gelu",
    norm_eps=1e-5,
    tie_embeddings=True,
    ssm=SSMConfig(state=16, head_dim=16, expand=2, n_groups=2, conv_kernel=4, chunk=8),
    hybrid_layer_ids=(2, 4),
    num_mem_blocks=2,
    adapter_rank=4,
    subquadratic=True,
)
