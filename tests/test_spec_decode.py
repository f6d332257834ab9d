"""Speculative decoding: unit + property tests for the draft/verify path.

The end-to-end contract (greedy spec == sequential greedy, bitwise, on
arbitrary streams) lives in the cross-engine fuzzer
(``test_engine_fuzz.py::test_fuzz_spec_parity``).  This file pins the
pieces that make that contract hold:

* :class:`RecurrentCache` snapshot/rollback is a bitwise per-lane select
  across every family's ``recurrent_leaf_axes`` layout — a lane that
  advanced ``j <= k`` speculative steps and then rejected is bitwise the
  state it had before advancing (property test, both recurrent archs).
* KV truncate-on-reject conserves the block pool: rejected positions
  never leak blocks or refs, and shared prefix blocks are never written
  past the committed length (the per-step invariant sweep enforces both
  while a rejection-heavy draft hammers the rollback path).
* Cross-feature races: cancel and deadline expiry landing between verify
  rounds refund fully; preempt-during-verify requeues only committed
  tokens; a spec lane spills/restores through the host tier O(copy); a
  seeded fault schedule over a spec engine never raises and keeps "ok"
  requests bitwise.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_smoke_config
from repro.core.aot import AotCache
from repro.models import registry
from repro.serve import EngineConfig, FaultPlan, ServeEngine
from repro.serve.cache import RecurrentCache

from test_engine_fuzz import (
    MAX_LEN, MAX_SLOTS, MODES, SPEC_K, _draft_mix, _FakeClock, drive,
    drive_chaos, make_stream, spec_modes,
)

# zamba at both of its structures (one shared block / two alternating)
REC_ARCHS = ("xlstm-1.3b", "zamba2-1.2b", "zamba2-7b")


@pytest.fixture(scope="module")
def setup():
    from repro.launch.mesh import single_device_mesh
    from repro.models.common import ShardRules

    mesh = single_device_mesh()
    rules = ShardRules.for_mesh(mesh)
    cfg = dataclasses.replace(
        get_smoke_config("smollm-360m"), compute_dtype="float32")
    params = registry.get_module(cfg).init(cfg, jax.random.PRNGKey(0))
    dparams = _draft_mix(cfg, params, 0.15)
    return cfg, mesh, rules, params, dparams, AotCache("spec")


# ---------------------------------------------------------------------------
# Config validation
# ---------------------------------------------------------------------------


def test_spec_config_validation(setup):
    cfg, mesh, rules, params, dparams, aot = setup
    base = dict(max_slots=MAX_SLOTS, max_len=MAX_LEN)
    with pytest.raises(ValueError, match="spec_k"):
        ServeEngine(cfg, mesh, rules, params,
                    EngineConfig(**base, spec_draft=cfg), aot=aot)
    with pytest.raises(ValueError, match="spec_draft"):
        ServeEngine(cfg, mesh, rules, params,
                    EngineConfig(**base, spec_k=3), aot=aot)
    with pytest.raises(ValueError, match="vocab"):
        bad = dataclasses.replace(cfg, vocab=cfg.vocab + 64)
        ServeEngine(cfg, mesh, rules, params,
                    EngineConfig(**base, spec_draft=bad, spec_k=3), aot=aot)
    with pytest.raises(ValueError, match="draft_params"):
        ServeEngine(cfg, mesh, rules, params, EngineConfig(**base),
                    aot=aot, draft_params=dparams)


# ---------------------------------------------------------------------------
# RecurrentCache snapshot/rollback: bitwise per-lane select (property)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module", params=REC_ARCHS)
def rec_cfg(request):
    return dataclasses.replace(
        get_smoke_config(request.param), compute_dtype="float32")


def _random_recurrent_leaves(cfg, rec, rng, slots=MAX_SLOTS, length=32):
    """Random arrays in each recurrent leaf's real shape/dtype."""
    sds = registry.get_module(cfg).make_cache_specs(cfg, slots, length)
    out = {}
    for name in rec.leaf_axes:
        sd = sds[name]
        if np.issubdtype(np.dtype(sd.dtype), np.integer):
            arr = rng.integers(0, 7, sd.shape).astype(sd.dtype)
        else:
            arr = rng.standard_normal(sd.shape).astype(sd.dtype)
        out[name] = jnp.asarray(arr)
    return out


@settings(max_examples=15, deadline=None)
@given(j=st.integers(min_value=0, max_value=SPEC_K + 1),
       seed=st.integers(min_value=0, max_value=10_000))
def test_snapshot_rollback_bitwise(rec_cfg, j, seed):
    """Snapshot, advance ``j <= k+1`` whole-state rewrites (a decode step
    rewrites the entire recurrent state), then roll back with a random
    keep mask: kept lanes are bitwise the advanced state, rolled-back
    lanes are bitwise the snapshot — for every leaf and every lane-axis
    layout the family declares.  ``j == 0`` pins the degenerate
    never-advanced case (rollback must still be an exact identity)."""
    rec = RecurrentCache(rec_cfg)
    assert rec, f"{rec_cfg.family} declares no recurrent leaves"
    rng = np.random.default_rng(seed)
    cache0 = _random_recurrent_leaves(rec_cfg, rec, rng)
    snap = rec.snapshot(cache0)
    cache = dict(cache0)
    for _ in range(j):
        cache = {
            n: c * np.asarray(1.25, c.dtype)
            + jnp.asarray(rng.standard_normal(c.shape).astype(c.dtype))
            if not np.issubdtype(np.dtype(c.dtype), np.integer)
            else c + 1
            for n, c in cache.items()
        }
    keep = rng.integers(0, 2, MAX_SLOTS).astype(bool)
    out = rec.rollback(cache, snap, jnp.asarray(keep))
    for name, axis in rec.leaf_axes.items():
        got = np.asarray(out[name])
        adv = np.asarray(cache[name])
        orig = np.asarray(cache0[name])
        for lane in range(MAX_SLOTS):
            ref = adv if keep[lane] else orig
            np.testing.assert_array_equal(
                np.take(got, lane, axis=axis),
                np.take(ref, lane, axis=axis),
                err_msg=f"{rec_cfg.family} leaf {name!r} lane {lane} "
                        f"(keep={bool(keep[lane])}, j={j})")


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_rollback_composes_with_freeze(rec_cfg, seed):
    """The verify program's per-step ladder composes rollback with the
    evict-time freeze: frozen (inactive) lanes stay exactly zero through
    a rollback, and rolled-back active lanes are untouched by a freeze
    that keeps them."""
    rec = RecurrentCache(rec_cfg)
    rng = np.random.default_rng(seed)
    cache = _random_recurrent_leaves(rec_cfg, rec, rng)
    active = rng.integers(0, 2, MAX_SLOTS).astype(bool)
    keep = rng.integers(0, 2, MAX_SLOTS).astype(bool)
    snap = rec.snapshot(cache)
    frozen = rec.freeze(cache, jnp.asarray(active))
    out = rec.rollback(frozen, snap, jnp.asarray(keep))
    inactive = [i for i in range(MAX_SLOTS) if not active[i] and keep[i]]
    assert rec.lanes_are_zero(out, inactive)
    for name, axis in rec.leaf_axes.items():
        for lane in range(MAX_SLOTS):
            if active[lane] and keep[lane]:
                np.testing.assert_array_equal(
                    np.take(np.asarray(out[name]), lane, axis=axis),
                    np.take(np.asarray(cache[name]), lane, axis=axis))


# ---------------------------------------------------------------------------
# KV truncate-on-reject: block-pool conservation under heavy rejection
# ---------------------------------------------------------------------------


def test_kv_truncate_on_reject_conserves_pool(setup):
    """A rejection-heavy draft (pure fresh init) forces the KV truncate
    path nearly every round on a paged + prefix-cached engine.  The
    per-step invariant sweep inside ``drive`` enforces the two
    conservation properties on every step: free + live + cached == pool
    capacity with exact refcounts, and any block mapped past a lane's
    committed length holds refcount 1 (a shared published block is never
    written by speculation).  Afterward the pool drains to zero in-use
    and the stream is still bitwise the sequential engine's."""
    cfg, mesh, rules, params, _, aot = setup
    junk = _draft_mix(cfg, params, 1.0)      # draft == fresh init
    stream = make_stream(np.random.default_rng(42), cfg.vocab)
    want, _ = drive(cfg, mesh, rules, params, aot, MODES["slotted"], stream)
    got, eng = drive(cfg, mesh, rules, params, aot,
                     spec_modes(cfg)["spec_prefix"], stream,
                     draft_params=junk)
    assert got == want
    assert eng.counters["spec_rejected"] > 0, "junk draft never rejected?"
    assert eng.alloc.in_use == 0
    assert eng.alloc.num_free + eng.alloc.num_cached == eng.alloc.capacity
    # a junk draft must not stall progress: every round still commits the
    # target's own sample, so throughput floors at sequential decode
    assert eng.stats["tokens_per_decode_dispatch"] >= 1.0


def test_spec_counters_and_stats(setup):
    """Counter accounting on a clean run: every non-replay verify round
    commits at least one token (the target's sample for the pending
    position), and with a draft close to the target both halves of the
    accept rule fire and committed/lane-rounds exceeds 1.0; the
    acceptance rate is a valid ratio; and a non-spec engine reports
    zeroed spec stats."""
    cfg, mesh, rules, params, dparams, aot = setup
    stream = make_stream(np.random.default_rng(43), cfg.vocab)
    _, eng = drive(cfg, mesh, rules, params, aot,
                   spec_modes(cfg)["spec_slotted"], stream,
                   draft_params=dparams)
    st_ = eng.stats
    assert eng.counters["spec_rounds"] > 0
    assert eng.counters["spec_accepted"] > 0
    assert eng.counters["spec_rejected"] > 0
    # speculation pays for its verify dispatches
    assert st_["tokens_per_decode_dispatch"] > 1.0
    assert 0.0 <= st_["spec_acceptance_rate"] <= 1.0
    assert st_["spec_acceptance_rate"] == pytest.approx(
        eng.counters["spec_accepted"] / max(1, eng.counters["spec_drafted"]))
    # a non-spec engine never touches the spec counters and (like the
    # paged-only keys) doesn't report the spec stats at all
    _, plain = drive(cfg, mesh, rules, params, aot, MODES["slotted"], stream)
    assert plain.counters["spec_rounds"] == 0
    assert "tokens_per_decode_dispatch" not in plain.stats
    assert "spec_acceptance_rate" not in plain.stats


# ---------------------------------------------------------------------------
# Cross-feature races
# ---------------------------------------------------------------------------


def test_cancel_between_verify_rounds_refunds(setup):
    """Cancel landing between verify rounds: the lane's blocks and
    deficit refund fully, the cancelled stream is a prefix of the
    sequential stream, and the surviving request is untouched."""
    cfg, mesh, rules, params, dparams, aot = setup
    prompts = [np.arange(1, 10, dtype=np.int32),
               np.arange(3, 15, dtype=np.int32)]
    stream = [(0, p, 12) for p in prompts]
    want, _ = drive(cfg, mesh, rules, params, aot, MODES["slotted"], stream)
    eng = ServeEngine(cfg, mesh, rules, params,
                      spec_modes(cfg)["spec_prefix"], aot=aot,
                      draft_params=dparams)
    for i, p in enumerate(prompts):
        eng.submit(p, max_new_tokens=12, rid=i)
    eng.step()
    eng.step()                      # a couple of verify rounds committed
    emitted = len(eng.live[0].tokens)
    assert emitted >= 1
    assert eng.cancel(0)
    eng.check_invariants()
    guard = 0
    while eng.has_work():
        eng.step()
        eng.check_invariants()
        guard += 1
        assert guard < 100
    c0, c1 = eng.completions[0], eng.completions[1]
    assert c0.status == "cancelled"
    assert list(c0.tokens) == want[0][: len(c0.tokens)]
    assert len(c0.tokens) >= emitted
    assert c1.status == "ok" and list(c1.tokens) == want[1]
    assert eng.alloc.in_use == 0


def test_deadline_expiry_mid_speculation(setup):
    """A TTL expiring while a lane is mid-speculation: the emitted
    (committed) prefix survives on the timed-out completion, blocks
    refund, and nothing past the committed stream ever leaks out —
    verify-round overshoot is never visible."""
    cfg, mesh, rules, params, dparams, aot = setup
    prompt = np.arange(2, 11, dtype=np.int32)
    want, _ = drive(cfg, mesh, rules, params, aot, MODES["slotted"],
                    [(0, prompt, 30)])
    clock = _FakeClock()
    eng = ServeEngine(cfg, mesh, rules, params,
                      spec_modes(cfg)["spec_slotted"], aot=aot,
                      draft_params=dparams, clock=clock)
    eng.submit(prompt, max_new_tokens=30, rid=0, deadline_s=2.5)
    guard = 0
    while eng.has_work():
        eng.step()
        eng.check_invariants()
        clock.t += 1.0
        guard += 1
        assert guard < 100
    c = eng.completions[0]
    assert c.status == "timeout"
    assert 0 < len(c.tokens) < 30
    assert list(c.tokens) == want[0][: len(c.tokens)]


def test_preempt_during_speculation_requeues_committed_only(setup):
    """Host preempt with a lane mid-speculation: the requeued resume
    carries exactly the committed tokens (replay count == committed
    emissions at preempt time), and the finished stream is bitwise the
    sequential engine's — an overshoot position surviving the preempt
    would diverge here."""
    cfg, mesh, rules, params, dparams, aot = setup
    prompt = np.arange(5, 14, dtype=np.int32)
    stream = [(0, prompt, 10)]
    want, _ = drive(cfg, mesh, rules, params, aot, MODES["slotted"], stream)
    eng = ServeEngine(cfg, mesh, rules, params,
                      spec_modes(cfg)["spec_slotted"], aot=aot,
                      draft_params=dparams)
    eng.submit(prompt, max_new_tokens=10, rid=0)
    eng.step()
    committed = len(eng.live[0].tokens)
    assert 1 <= committed <= SPEC_K + 1
    eng.preempt(0)
    eng.check_invariants()
    guard = 0
    while eng.has_work():
        eng.step()
        eng.check_invariants()
        guard += 1
        assert guard < 100
    assert eng.counters["preemptions"] == 1
    assert eng.counters["replayed_tokens"] == committed
    c = eng.completions[0]
    assert c.status == "ok" and list(c.tokens) == want[0]


def test_spec_lane_spills_and_restores_o_copy(setup):
    """A spec lane through the host tier: preempt spills the lane's
    blocks to host RAM, the resume restores them O(copy) — zero replayed
    decode steps, zero re-prefilled prompt tokens — and the draft cache
    is rebuilt from the committed history so speculation continues
    bitwise."""
    cfg, mesh, rules, params, dparams, aot = setup
    prompt = np.arange(7, 19, dtype=np.int32)
    stream = [(0, prompt, 10)]
    want, _ = drive(cfg, mesh, rules, params, aot, MODES["slotted"], stream)
    eng = ServeEngine(cfg, mesh, rules, params,
                      spec_modes(cfg)["spec_tiered"], aot=aot,
                      draft_params=dparams)
    eng.submit(prompt, max_new_tokens=10, rid=0)
    eng.step()
    assert len(eng.live[0].tokens) >= 1
    eng.preempt(0)
    eng.check_invariants()
    guard = 0
    while eng.has_work():
        eng.step()
        eng.check_invariants()
        guard += 1
        assert guard < 100
    assert eng.counters["spills"] >= 1
    assert eng.counters["restores"] >= 1
    assert eng.counters["replayed_tokens"] == 0, (
        "tier restore replayed decode steps — resume must be O(copy)")
    eng.tier.check()
    assert eng.tier.spilled_lanes == 0
    c = eng.completions[0]
    assert c.status == "ok" and list(c.tokens) == want[0]


def test_spec_chaos_never_raises(setup):
    """A seeded fault schedule (corrupted verify fetches, failed prefill
    and alloc, lost sched pushes) over a preempting spec engine: step()
    never raises, invariants hold every step, and every request that
    finishes "ok" is bitwise the fault-free sequential stream."""
    cfg, mesh, rules, params, dparams, aot = setup
    rates = {"decode_logits": 0.1, "prefill": 0.1, "alloc": 0.05,
             "sched_push": 0.1}
    detected = 0
    for seed in range(3):
        rng = np.random.default_rng(8800 + seed)
        stream = make_stream(rng, cfg.vocab)
        want, _ = drive(cfg, mesh, rules, params, aot, MODES["slotted"],
                        stream)
        eng = drive_chaos(cfg, mesh, rules, params, aot,
                          spec_modes(cfg)["spec_preempt"], stream,
                          FaultPlan(seed, rates), deadline_every=4,
                          cancel_ticks={int(rng.integers(1, 20))},
                          draft_params=dparams)
        for rid in range(len(stream)):
            c = eng.completions[rid]
            assert c.status in ("ok", "timeout", "cancelled", "failed")
            got = list(c.tokens)
            if c.status == "ok":
                assert got == want[rid], (
                    f"seed={seed} rid={rid}: ok spec request diverged "
                    f"under faults\n  want={want[rid]}\n  got ={got}")
            else:
                assert got == want[rid][: len(got)]
        assert eng.alloc.in_use == 0
        detected += eng.stats["faults_detected"]
    assert detected > 0, "no fault ever detected (vacuous chaos run)"


def test_spec_prebuild_keeps_builds_flat(setup):
    """After prebuild, a spec drive — admissions, verify rounds, draft
    rebuilds, preempts — dispatches purely from the AOT cache."""
    cfg, mesh, rules, params, dparams, aot = setup
    ec = spec_modes(cfg)["spec_preempt"]
    ServeEngine(cfg, mesh, rules, params, ec, aot=aot,
                draft_params=dparams).prebuild()
    builds0 = aot.stats["builds"]
    stream = make_stream(np.random.default_rng(44), cfg.vocab)
    drive(cfg, mesh, rules, params, aot, ec, stream, draft_params=dparams)
    assert aot.stats["builds"] == builds0, (
        "spec decode built executables after prebuild")
