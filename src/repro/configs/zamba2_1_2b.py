"""Zamba2-1.2B — hybrid Mamba2 + shared attention block [arXiv:2411.15242].

38 Mamba2 layers at d_model=2048 (ssm_state 64, SSD heads of 64), vocab
32000; some layers first run the one shared attention+MLP block (32 MHA
heads over concat(hidden, embedding), 4096 wide; MLP 8192, GELU), with
LoRA adapters of their own on the MLP and on q/k/v (models/zamba.py).
Sub-quadratic: eligible for long_500k.

The widths above are the published ones.  Not read from the published
config.json, which this repository does not hold: ``hybrid_layer_ids``
(taken as every sixth layer from layer 5), ``adapter_rank`` (128, as the
7B's), ``n_groups`` (1), ``norm_eps`` (1e-5), tied embeddings, and the
attention adapter being on.
"""
from repro.configs.base import ArchConfig, SSMConfig

CONFIG = ArchConfig(
    name="zamba2-1.2b",
    family="hybrid",
    n_layers=38,
    d_model=2048,
    n_heads=32,
    n_kv=32,
    d_ff=8192,
    vocab=32_000,
    d_head=128,
    query_scale=(128 / 2) ** -0.5,
    gate_act="gelu",
    norm_eps=1e-5,
    tie_embeddings=True,
    ssm=SSMConfig(state=64, head_dim=64, expand=2, n_groups=1, conv_kernel=4, chunk=256),
    hybrid_layer_ids=(5, 11, 17, 23, 29, 35),
    num_mem_blocks=1,
    adapter_rank=128,
    use_shared_attention_adapter=True,
    subquadratic=True,
)

SMOKE = ArchConfig(
    name="zamba2-1.2b-smoke",
    family="hybrid",
    n_layers=5,
    d_model=64,
    n_heads=4,
    n_kv=4,
    d_ff=128,
    vocab=256,
    d_head=32,
    query_scale=(32 / 2) ** -0.5,
    gate_act="gelu",
    norm_eps=1e-5,
    tie_embeddings=True,
    ssm=SSMConfig(state=16, head_dim=16, expand=2, n_groups=1, conv_kernel=4, chunk=8),
    hybrid_layer_ids=(1, 3),
    num_mem_blocks=1,
    adapter_rank=4,
    use_shared_attention_adapter=True,
    subquadratic=True,
)
