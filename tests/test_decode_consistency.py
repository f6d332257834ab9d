"""Strong serving correctness: prefill logits == forward logits at the
last position, and the first decode step == forward at the next position.
Run in f32 so the comparison is tight."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke_config
from repro.models import registry


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_then_decode_matches_forward(arch, mesh, rules, key):
    cfg = dataclasses.replace(
        get_smoke_config(arch), compute_dtype="float32")
    if cfg.moe.num_experts:
        # no-drop capacity: GShard drops depend on how many tokens share the
        # batch, so prefill-vs-decode would legitimately diverge otherwise
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(
                cfg.moe, capacity_factor=float(cfg.moe.num_experts)))
    mod = registry.get_module(cfg)
    params = mod.init(cfg, key)
    B, S = 2, 24
    s_text = S - (cfg.frontend_tokens if cfg.family == "vlm" else 0)
    tokens = jax.random.randint(key, (B, s_text + 1), 0, cfg.vocab)
    extra = None
    if cfg.family == "vlm":
        extra = jax.random.normal(key, (B, cfg.frontend_tokens, cfg.frontend_dim))
    if cfg.family == "audio":
        extra = jax.random.normal(key, (B, cfg.enc_seq, cfg.d_model))

    # teacher-forced loss over tokens[:, :S+1] gives logits via loss path;
    # instead compare prefill(t[:, :n]) vs prefill(t[:, :n+1]).
    n = s_text - 1
    cache, logits_a = jax.jit(
        lambda p, t, e: mod.prefill(cfg, mesh, rules, p, t, e,
                                    max_len=s_text + 8)
    )(params, tokens[:, :n], extra)
    _, logits_b = jax.jit(
        lambda p, t, e: mod.prefill(cfg, mesh, rules, p, t, e,
                                    max_len=s_text + 8)
    )(params, tokens[:, :n + 1], extra)

    seq = n + (cfg.frontend_tokens if cfg.family == "vlm" else 0)
    logits_d, _ = jax.jit(
        lambda p, c, t: mod.decode_step(cfg, mesh, rules, p, c, t,
                                        jnp.int32(seq))
    )(params, cache, tokens[:, n].astype(jnp.int32))

    # decoding token n (with cache of the first n) == prefill over n+1 tokens
    np.testing.assert_allclose(
        np.asarray(logits_d), np.asarray(logits_b),
        atol=2e-3, rtol=2e-3,
    ), arch


@pytest.mark.parametrize("arch", ["smollm-360m", "gemma2-27b"])
def test_slotted_decode_position_minor_matches_before(arch, mesh, rules, key):
    """One slotted decode step in the (L[,2], B, Hk, dh, S) cache, lanes at
    lengths 0, mid and max_len - 1 over garbage beyond each length (gemma2:
    windowed local layers, softcaps): the logits, greedy tokens and
    written cache of the (B, S, Hk, dh) path — ``decode_attention`` on
    each layer transposed — and the full forward's logits there."""
    from repro.models import lm
    from repro.models.attention import DecodeSharding, decode_attention

    cfg = dataclasses.replace(get_smoke_config(arch), compute_dtype="float32")
    params = lm.init(cfg, key)
    S, lens = 32, [0, 9, 31]
    rng = np.random.default_rng(11)
    seqs = [rng.integers(0, cfg.vocab, n + 1).astype(np.int32) for n in lens]
    cache = {name: jnp.asarray(rng.normal(size=sd.shape), sd.dtype)
             for name, sd in lm.make_cache_specs(cfg, len(lens), S).items()}
    prefill = jax.jit(lambda c, t, slot, plen: lm.prefill_slot(
        cfg, mesh, rules, params, c, t, slot, plen))
    for lane, n in enumerate(lens):
        if n:
            prompt = np.zeros((1, S), np.int32)
            prompt[0, :n] = seqs[lane][:n]
            cache, _ = prefill(cache, jnp.asarray(prompt), lane, n)
    toks = jnp.asarray([s[n] for s, n in zip(seqs, lens)], jnp.int32)
    cur = jnp.asarray(lens, jnp.int32)
    got, got_cache = jax.jit(lambda c: lm.decode_step(
        cfg, mesh, rules, params, c, toks, cur))(cache)

    dec = DecodeSharding.choose(mesh, len(lens))
    bshd = lambda c: jnp.moveaxis(c, -1, 1)            # (B, S, Hk, dh)

    def before(q, kc, vc, k, v, window):
        o, kc, vc = decode_attention(q, bshd(kc), bshd(vc), k, v, cur,
                                     sharding=dec, window=window,
                                     softcap=cfg.attn_softcap)
        return o, jnp.moveaxis(kc, 1, -1), jnp.moveaxis(vc, 1, -1)

    def walk_before(c):
        x = lm.embed_tokens(cfg, rules, params, toks[:, None])[:, 0]
        return lm._decode_walk(cfg, mesh, rules, params, c, x, cur, None,
                               before)

    want, want_cache = jax.jit(walk_before)(cache)
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    for name in ("k", "v"):
        np.testing.assert_allclose(np.asarray(got_cache[name]),
                                   np.asarray(want_cache[name]),
                                   atol=1e-5, rtol=1e-5)
    fwd = jax.jit(lambda t: lm.unembed(cfg, rules, params, lm.forward(
        cfg, mesh, rules, params, t, remat=False)[0][:, -1]))
    for lane, seq in enumerate(seqs):
        np.testing.assert_allclose(got[lane], np.asarray(fwd(seq[None]))[0],
                                   atol=2e-3, rtol=2e-3)
