"""Batched serving entry points.

``generate`` is a thin compatibility wrapper over the continuous-batching
:class:`~repro.serve.engine.ServeEngine`: all prompts are submitted at
once into a ``max_slots = batch`` engine, so its behavior (greedy tokens
included — asserted in tests/test_serve_engine.py) matches the legacy
static loop while routing through the slotted cache, fused sampling, and
the AOT dispatch cache.

``generate_static`` is the legacy fixed-batch loop — one prefill, then
every sequence decodes to the full token budget with logits round-tripping
to host sampling each step.  It remains as the fallback for families the
slot engine doesn't cover (modality frontends with extra inputs) and as
the reference the engine's greedy parity tests compare against.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import registry
from repro.models.common import ShardRules
from repro.serve.step import jit_decode_step, jit_prefill


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_new_tokens: int = 16
    temperature: float = 0.0       # 0 => greedy
    seed: int = 0


def generate(
    cfg: ArchConfig,
    mesh,
    rules: ShardRules,
    params,
    prompts: np.ndarray,           # (B, S) int32
    extra=None,                    # vlm patches / audio frames
    serve: ServeConfig | None = None,
) -> np.ndarray:
    """Returns (B, max_new_tokens) int32 generated tokens."""
    serve = serve or ServeConfig()
    if extra is not None or not registry.supports_slot_serving(cfg):
        return generate_static(cfg, mesh, rules, params, prompts, extra, serve)

    from repro.serve.engine import EngineConfig, ServeEngine

    B, S = prompts.shape
    engine = ServeEngine(
        cfg, mesh, rules, params,
        EngineConfig(
            max_slots=B,
            max_len=S + serve.max_new_tokens,
            seed=serve.seed,
            # the wrapper serves equal-length prompts: one exact bucket
            prefill_buckets=(S,),
        ),
    )
    out = engine.run(
        list(np.asarray(prompts, np.int32)),
        max_new_tokens=serve.max_new_tokens,
        temperature=serve.temperature,
    )
    return np.stack(out, axis=0)


def generate_static(
    cfg: ArchConfig,
    mesh,
    rules: ShardRules,
    params,
    prompts: np.ndarray,
    extra=None,
    serve: ServeConfig | None = None,
) -> np.ndarray:
    """Legacy static-batch loop: prefill once, decode the whole batch to the
    full budget with host-side sampling (the pre-engine behavior)."""
    serve = serve or ServeConfig()
    B, S = prompts.shape
    n_ctx = S + (cfg.frontend_tokens if cfg.family == "vlm" else 0)
    max_len = n_ctx + serve.max_new_tokens
    shape = ShapeConfig("serve", "prefill", S, B)
    prefill_fn, _ = jit_prefill(cfg, mesh, rules, shape, max_len=max_len)
    cache, logits = prefill_fn(params, jnp.asarray(prompts), extra)

    dshape = ShapeConfig("serve", "decode", max_len, B)
    decode_fn, _ = jit_decode_step(cfg, mesh, rules, dshape)

    key = jax.random.PRNGKey(serve.seed)
    out = []
    cur = n_ctx
    for t in range(serve.max_new_tokens):
        if serve.temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, logits / serve.temperature, axis=-1)
        else:
            tok = jnp.argmax(logits, axis=-1)
        tok = tok.astype(jnp.int32)
        out.append(np.asarray(tok))
        logits, cache = decode_fn(params, cache, tok, jnp.int32(cur))
        cur += 1
    return np.stack(out, axis=1)
