"""Per-architecture smoke tests (deliverable f): reduced same-family
configs run one forward/train step on CPU, asserting shapes + finiteness.
The FULL configs are exercised via the dry-run only."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.configs.base import ShapeConfig
from repro.models import registry
from repro.optim import OptConfig
from repro.train.step import TrainSettings, build_train_step
from repro.optim import init_state


def _batch(cfg, B, S, key):
    s_text = S - (cfg.frontend_tokens if cfg.family == "vlm" else 0)
    b = {"tokens": jax.random.randint(key, (B, s_text + 1), 0, cfg.vocab)}
    if cfg.family == "vlm":
        b["patch_embeds"] = jax.random.normal(
            key, (B, cfg.frontend_tokens, cfg.frontend_dim), jnp.bfloat16)
    if cfg.family == "audio":
        b["frames"] = jax.random.normal(
            key, (B, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
    return b


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch, mesh, rules, key):
    cfg = get_smoke_config(arch)
    mod = registry.get_module(cfg)
    params = mod.init(cfg, key)
    batch = _batch(cfg, 2, 32, key)

    loss, metrics = jax.jit(
        lambda p, b: mod.loss_fn(cfg, mesh, rules, p, b)
    )(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), f"{arch}: non-finite loss"
    assert np.isfinite(float(metrics["ce_loss"]))

    # one full train step (grads + adam update): params change, stay finite
    opt = OptConfig(kind="adam", lr=1e-3)
    step = build_train_step(cfg, mesh, rules, opt, TrainSettings(num_slices=2))
    opt_state = init_state(opt, params)
    new_params, new_opt, m2 = jax.jit(step)(params, opt_state, batch)
    assert np.isfinite(float(m2["loss"]))
    changed = jax.tree.map(
        lambda a, b: bool(np.any(np.asarray(a) != np.asarray(b))),
        params, new_params)
    assert any(jax.tree.leaves(changed)), f"{arch}: update did not change params"
    for leaf in jax.tree.leaves(new_params):
        assert np.all(np.isfinite(np.asarray(leaf, dtype=np.float32)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """Pin the exact published configs (guards accidental edits)."""
    cfg = get_config(arch)
    expect = {
        "deepseek-67b": (95, 8192, 64, 8, 22016, 102_400),
        "smollm-360m": (32, 960, 15, 5, 2560, 49_152),
        "stablelm-12b": (40, 5120, 32, 8, 13_824, 100_352),
        "gemma2-27b": (46, 4608, 32, 16, 36_864, 256_000),
        "internvl2-76b": (80, 8192, 64, 8, 28_672, 128_256),
        "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32_000),
        "zamba2-7b": (81, 3584, 32, 32, 14_336, 32_000),
        "whisper-tiny": (4, 384, 6, 6, 1536, 51_865),
        "xlstm-1.3b": (48, 2048, 4, 4, 0, 50_304),
        "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 0, 151_936),
        "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 0, 151_936),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.d_ff, cfg.vocab)
    assert got == expect, f"{arch}: {got} != {expect}"
    if arch.startswith("qwen3-moe"):
        assert cfg.moe.num_experts == 128 and cfg.moe.top_k == 8
        assert cfg.moe.d_expert == (768 if "30b" in arch else 1536)
    if arch == "zamba2-1.2b":
        assert cfg.ssm.state == 64 and cfg.subquadratic
    if arch == "zamba2-7b":
        assert cfg.ssm.state == 64 and cfg.ssm.n_groups == 2
        assert cfg.n_heads * cfg.head_dim == 2 * cfg.d_model == 7168
        assert cfg.num_mem_blocks == 2 and cfg.adapter_rank == 128
        assert cfg.hybrid_layer_ids[:2] == (6, 11) and len(cfg.hybrid_layer_ids) == 13
    if arch == "gemma2-27b":
        assert cfg.alt_local_global and cfg.attn_softcap == 50.0 \
            and cfg.logit_softcap == 30.0
    if arch == "xlstm-1.3b":
        assert cfg.subquadratic
