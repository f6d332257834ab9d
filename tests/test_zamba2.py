"""Zamba2 at its published block (models/zamba.py) against the plain
reference (bench/refs/zamba2-7b.py), on seeded weights mapped through the
benchmark's adapter, at a tiny size with the 7B's structure: 6 layers,
hybrid layers 2 and 4 on two alternating shared blocks, 2 SSD groups,
attention heads of 24 (not a power of two), adapter rank 4.  The program
computes in float32 here, so the two agree to float32 rounding."""
import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from repro.configs import get_smoke_config
from repro.models import registry, ssm, zamba
from repro.models.common import rms_norm
from repro.serve import EngineConfig, ServeEngine

ROOT = pathlib.Path(__file__).resolve().parents[1]
TINY = dict(
    hidden_size=48, attention_hidden_size=96, attention_head_dim=24,
    num_attention_heads=4, num_key_value_heads=4, intermediate_size=96,
    ffn_hidden_size=96, adapter_rank=4, mamba_headdim=16, n_mamba_heads=6,
    mamba_d_state=16, chunk_size=8, num_hidden_layers=6,
    layers_block_type=["mamba", "mamba", "hybrid", "mamba", "hybrid", "mamba"],
    hybrid_layer_ids=[2, 4], vocab_size=256, compute_dtype="float32")


@pytest.fixture(scope="module")
def setup():
    ref = harness.load_module(ROOT / "bench/refs/zamba2-7b.py")
    adapter = harness.load_module(ROOT / "bench/adapters/zamba2-7b.py")
    cfgd = {**json.loads((ROOT / "bench/configs/zamba2-7b.json").read_text()),
            **TINY}
    cfg = adapter.arch(cfgd)
    w = ref.init(cfgd, jax.random.PRNGKey(3))
    params = adapter.to_program(w)
    return ref, cfgd, cfg, w, params


def test_tiny_config_is_the_smoke_config(setup):
    """The adapter reads the published keys into the same structure the
    registry's smoke config declares."""
    _, _, cfg, _, params = setup
    smoke = dataclasses.replace(get_smoke_config("zamba2-7b"),
                                name=cfg.name, compute_dtype="float32")
    assert cfg == smoke
    want = registry.abstract_params(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
    assert zamba.runs(cfg) == [(0, 2, None), (2, 4, 0), (4, 6, 1)]


def test_forward_logits_match_reference(setup, mesh, rules):
    ref, cfgd, cfg, w, params = setup
    tokens = np.random.default_rng(0).integers(0, cfg.vocab, (2, 21), np.int32)
    with jax.default_matmul_precision("highest"):
        hidden, _ = zamba.forward(cfg, mesh, rules, params, tokens, remat=False)
        got = zamba._logits(cfg, params, hidden)
        want = ref.logits(cfgd, w, jnp.asarray(tokens))
    # float32 on both sides; the chunked SSD and the sequential scan sum
    # in different orders, so rounding of a few ulps of the logits' scale
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_loss_and_gradients_match_reference(setup, mesh, rules):
    ref, cfgd, cfg, w, params = setup
    tokens = np.random.default_rng(1).integers(0, cfg.vocab, (2, 17), np.int32)
    adapter = harness.load_module(ROOT / "bench/adapters/zamba2-7b.py")
    with jax.default_matmul_precision("highest"):
        (got, _), g_prog = jax.value_and_grad(
            lambda p: zamba.loss_fn(cfg, mesh, rules, p, {"tokens": tokens},
                                    remat=False), has_aux=True)(params)
        want, g_ref = jax.value_and_grad(
            lambda w: ref.loss(cfgd, w, jnp.asarray(tokens)))(w)
    # float32 rounding of a mean over 32 positions
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    g_prog = adapter.from_program(g_prog)
    for a, b in zip(jax.tree.leaves(g_prog), jax.tree.leaves(g_ref)):
        # gradients of the same float32 sums in other orders: relative to
        # the leaf's norm
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * max(
            float(jnp.linalg.norm(b)), 1e-6)


def test_reference_ssd_matches_stepwise_recurrence(setup):
    """The reference's scan, and the program's chunked SSD with two
    groups, against the recurrence written out position by position."""
    ref = setup[0]
    rng = np.random.default_rng(2)
    B, T, H, P, G, N = 2, 13, 4, 3, 2, 5
    x = rng.normal(size=(B, T, H, P)).astype(np.float32)
    dt = rng.uniform(0.01, 0.5, (B, T, H)).astype(np.float32)
    A = -rng.uniform(1.0, 4.0, H).astype(np.float32)
    Bm = rng.normal(size=(B, T, G, N)).astype(np.float32)
    Cm = rng.normal(size=(B, T, G, N)).astype(np.float32)
    want = np.zeros((B, T, H, P), np.float64)
    for b in range(B):
        for h in range(H):
            g = h // (H // G)
            state = np.zeros((N, P))
            for t in range(T):
                state = (np.exp(dt[b, t, h] * A[h]) * state
                         + dt[b, t, h] * np.outer(Bm[b, t, g], x[b, t, h]))
                want[b, t, h] = Cm[b, t, g] @ state
    args = tuple(jnp.asarray(a) for a in (x, dt, A, Bm, Cm))
    with jax.default_matmul_precision("highest"):
        got_ref = ref.ssd_scan(*args)
        got_prog = ssm.ssd_chunked(*args, chunk=4)
    # float32 against float64 sums of 13 terms of order one
    np.testing.assert_allclose(np.asarray(got_ref), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_prog), want, rtol=1e-4, atol=1e-4)


def test_grouped_gated_norm_is_a_norm_per_group():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 5, 12)).astype(np.float32))
    g = jnp.asarray(rng.normal(size=(12,)).astype(np.float32))
    got = ssm.group_rms_norm(x, g, 3, 1e-5)
    want = jnp.concatenate([rms_norm(x[..., 4 * i:4 * i + 4], g[4 * i:4 * i + 4],
                                     1e-5) for i in range(3)], axis=-1)
    # the same float32 operations, grouped by a reshape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(ssm.group_rms_norm(x, g, 1, 1e-5)),
                                  np.asarray(rms_norm(x, g, 1e-5)))


def test_slotted_prefill_then_decode_logits_match_reference(setup, mesh, rules):
    """The functions the engine composes, through the slotted cache of
    two lanes: prefill one lane from a right-padded bucket, then decode
    three tokens; each step's logits against the reference's full
    forward at that position."""
    ref, cfgd, cfg, w, params = setup
    rng = np.random.default_rng(4)
    seq = rng.integers(0, cfg.vocab, 12).astype(np.int32)
    plen, bucket, lane = 9, 16, 1
    sds = zamba.make_cache_specs(cfg, 2, 32)
    cache = {n: jnp.zeros(s.shape, s.dtype) for n, s in sds.items()}
    prompt = np.zeros((1, bucket), np.int32)
    prompt[0, :plen] = seq[:plen]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits(cfgd, w, jnp.asarray(seq[None])))[0]
        cache, lg = zamba.prefill_slot(cfg, mesh, rules, params, cache,
                                       jnp.asarray(prompt), lane, plen)
        got = [np.asarray(lg)[0]]
        for pos in range(plen, seq.size):
            toks = jnp.asarray(np.array([0, seq[pos]], np.int32))
            lg, cache = zamba.decode_step(cfg, mesh, rules, params, cache, toks,
                                          jnp.asarray([0, pos], jnp.int32))
            got.append(np.asarray(lg)[lane])
    # float32 on both sides (see test_forward_logits_match_reference)
    np.testing.assert_allclose(np.stack(got), want[plen - 1:], rtol=1e-4,
                               atol=1e-4)


def test_slotted_decode_at_mixed_lengths_matches_reference(setup, mesh,
                                                          rules):
    """One decode step of three lanes at lengths 0, 9 and max_len - 1 in
    the position-minor KV segment, with garbage beyond each length: each
    lane's logits and greedy token are the reference's at its position."""
    ref, cfgd, cfg, w, params = setup
    S, lens = 32, [0, 9, 31]
    rng = np.random.default_rng(6)
    seqs = [rng.integers(0, cfg.vocab, n + 1).astype(np.int32) for n in lens]
    sds = zamba.make_cache_specs(cfg, len(lens), S)
    assert sds["k"].shape == (2, len(lens), cfg.n_kv, cfg.head_dim, S)
    cache = {n: jnp.asarray(rng.normal(size=sd.shape), sd.dtype)
             if n in ("k", "v") else jnp.zeros(sd.shape, sd.dtype)
             for n, sd in sds.items()}
    prefill = jax.jit(lambda c, t, slot, plen: zamba.prefill_slot(
        cfg, mesh, rules, params, c, t, slot, plen))
    decode = jax.jit(lambda c, t, cur: zamba.decode_step(
        cfg, mesh, rules, params, c, t, cur))
    with jax.default_matmul_precision("highest"):
        for lane, n in enumerate(lens):
            if n:
                prompt = np.zeros((1, S), np.int32)
                prompt[0, :n] = seqs[lane][:n]
                cache, _ = prefill(cache, jnp.asarray(prompt), lane, n)
        got, _ = decode(
            cache, jnp.asarray([s[n] for s, n in zip(seqs, lens)], jnp.int32),
            jnp.asarray(lens, jnp.int32))
        want = np.stack([np.asarray(ref.logits(cfgd, w, jnp.asarray(s[None])))
                         [0, -1] for s in seqs])
    got = np.asarray(got)
    np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_engine_serves_the_reference_greedy_tokens(setup, mesh, rules):
    """ServeEngine prefill then decode, three requests through two lanes:
    every served token is the reference's best at its position, within
    float32 rounding of the logits."""
    ref, cfgd, cfg, w, params = setup
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in (7, 13, 5)]
    budgets = [6, 4, 8]
    eng = ServeEngine(cfg, mesh, rules, params,
                      EngineConfig(max_slots=2, max_len=32))
    rids = [eng.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)]
    eng.drain()
    for rid, prompt in zip(rids, prompts):
        served = np.asarray(eng.completions[rid].tokens, np.int32)
        seq = np.concatenate([prompt, served[:-1]])
        with jax.default_matmul_precision("highest"):
            lg = np.asarray(ref.logits(cfgd, w, jnp.asarray(seq[None])))[0]
        lg = lg[prompt.size - 1:]
        gap = lg.max(-1) - lg[np.arange(served.size), served]
        # the program in float32: a served token can lose to the
        # reference's best only by rounding of the logits (scale ~1)
        assert gap.max() < 1e-4, gap


def test_hybrid_engine_sets_state_byte_gauges(setup, mesh, rules):
    _, _, cfg, _, params = setup
    eng = ServeEngine(cfg, mesh, rules, params,
                      EngineConfig(max_slots=3, max_len=32))
    cache = eng.state["cache"]
    nbytes = lambda a: a.size * a.dtype.itemsize
    gauge = eng.obs.metrics.gauge
    assert gauge("engine.kv_bytes").value == nbytes(cache["k"]) + nbytes(cache["v"])
    assert gauge("engine.recurrent_bytes").value == (
        nbytes(cache["ssm"]) + nbytes(cache["conv"]))
    # two hybrid layers of 3 lanes x 32 positions x 4 heads x 24, k and v
    assert gauge("engine.kv_bytes").value == 2 * 2 * 3 * 32 * 4 * 24 * 4
