"""How the program runs Zamba2-7B-Instruct: its ``ArchConfig`` from the
published keys, and the benchmark's weights (bench/refs/zamba2-7b.py
layout) renamed into the program's parameter tree (models/zamba.py).  The
program writes an RMSNorm gain as 1 + gamma."""
from __future__ import annotations

# reference name -> the program's key, per group
NAMES = {"embed": "embed", "final_norm": "ln_f"}
GROUPS = {
    "mamba": ("mamba", {"norm": "ln", "in_proj": "in_proj", "conv_w": "conv_w",
                        "conv_b": "conv_b", "dt_bias": "dt_bias",
                        "A_log": "A_log", "D": "D_skip",
                        "gated_norm": "out_ln", "out_proj": "out_proj"}),
    "blocks": ("shared", {"attn_norm": "ln_attn", "q_proj": "wq",
                          "k_proj": "wk", "v_proj": "wv", "o_proj": "wo",
                          "mlp_norm": "ln_mlp", "gate_up": "w_gate_up",
                          "down": "w_down"}),
    "hybrid": ("hybrid", {"adapter_a": "mlp_a", "adapter_b": "mlp_b",
                          "linear": "linear"}),
}
GAINS = ("final_norm", "norm", "gated_norm", "attn_norm", "mlp_norm")


def arch(cfg: dict):
    from repro.configs.base import ArchConfig, SSMConfig

    d, dh = cfg["hidden_size"], cfg["attention_head_dim"]
    assert cfg["attention_hidden_size"] == 2 * d == cfg["num_attention_heads"] * dh
    assert cfg["n_mamba_heads"] * cfg["mamba_headdim"] == cfg["mamba_expand"] * d
    hybrid = tuple(i for i, t in enumerate(cfg["layers_block_type"]) if t == "hybrid")
    assert hybrid == tuple(cfg["hybrid_layer_ids"])
    return ArchConfig(
        name=cfg["name"], family="hybrid",
        n_layers=cfg["num_hidden_layers"], d_model=d,
        n_heads=cfg["num_attention_heads"], n_kv=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"], d_head=dh,
        query_scale=(dh / 2) ** -0.5, gate_act=cfg["hidden_act"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        ssm=SSMConfig(state=cfg["mamba_d_state"], head_dim=cfg["mamba_headdim"],
                      expand=cfg["mamba_expand"], n_groups=cfg["mamba_ngroups"],
                      conv_kernel=cfg["mamba_d_conv"], chunk=cfg["chunk_size"]),
        hybrid_layer_ids=hybrid, num_mem_blocks=cfg["num_mem_blocks"],
        adapter_rank=cfg["adapter_rank"],
        use_shared_attention_adapter=cfg["use_shared_attention_adapter"],
        subquadratic=True,
        param_dtype=cfg["param_dtype"], compute_dtype=cfg["compute_dtype"])


def to_program(w: dict) -> dict:
    """Reference weights -> the program's parameter tree (gains as 1 + g)."""
    def conv(name, x):
        return x - 1.0 if name in GAINS else x

    out = {NAMES[k]: conv(k, w[k]) for k in NAMES}
    for group, (key, names) in GROUPS.items():
        out[key] = {names[k]: conv(k, w[group][k]) for k in names}
    return out


def from_program(tree: dict) -> dict:
    """A tree in the program's layout (gradients, moments, differences of
    parameters) renamed into the reference's layout, values untouched."""
    out = {k: tree[v] for k, v in NAMES.items()}
    for group, (key, names) in GROUPS.items():
        out[group] = {k: tree[key][v] for k, v in names.items()}
    return out
