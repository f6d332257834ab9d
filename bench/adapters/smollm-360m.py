"""How the program runs SmolLM-360M: its ``ArchConfig``, and the benchmark's
weights (bench/refs/smollm-360m.py layout) renamed into the program's
parameter tree.  The program writes an RMSNorm gain as 1 + gamma."""
from __future__ import annotations


# reference name -> the program's key (under "blocks" for stacked layers)
NAMES = {"embed": "embed", "final_norm": "ln_f"}
LAYER_NAMES = {"attn_norm": "ln1", "wq": "wq", "wk": "wk", "wv": "wv",
               "wo": "wo", "mlp_norm": "ln2", "wg": "wg", "wu": "wu",
               "wd": "wd"}
GAINS = ("final_norm", "attn_norm", "mlp_norm")


def arch(cfg: dict):
    from repro.configs.base import ArchConfig

    return ArchConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        d_head=cfg["head_dim"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["param_dtype"], compute_dtype=cfg["compute_dtype"])


def to_program(w: dict) -> dict:
    """Reference weights -> the program's parameter tree (gains as 1 + g)."""
    def conv(name, x):
        return x - 1.0 if name in GAINS else x

    return {
        **{NAMES[k]: conv(k, w[k]) for k in NAMES},
        "blocks": {LAYER_NAMES[k]: conv(k, w["layers"][k]) for k in LAYER_NAMES},
    }


def from_program(tree: dict) -> dict:
    """A tree in the program's layout (gradients, moments, differences of
    parameters) renamed into the reference's layout, values untouched."""
    return {
        **{k: tree[v] for k, v in NAMES.items()},
        "layers": {k: tree["blocks"][v] for k, v in LAYER_NAMES.items()},
    }


