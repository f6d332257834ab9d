"""Cross-engine randomized parity fuzzer.

THE correctness property of the whole serve subsystem, in the paper's
terms: every memory-management strategy the engine layers on — paged
block tables, chunked prefill, refcounted prefix caching (COW tails,
decode-boundary publication), preempt-and-requeue admission — must be
*behavior-invisible*: token-for-token identical to the simple slotted
engine under greedy decoding, on arbitrary request streams.

Each seeded episode draws a random request stream (bursty arrivals,
shared and disjoint prompt prefixes, mixed lengths and budgets, natural
mid-stream evictions as budgets expire) and replays it through every
engine mode — paged, chunked, prefix-cached, preempting, and their
combinations; after every step the paged engines run the full allocator
invariant sweep (refcount conservation, free + live + cached == pool,
compaction, no KV position outside its lane's mapped blocks).

Episode count: ``ENGINE_FUZZ_EPISODES`` env var (default below);
``scripts/ci.sh`` runs the 200-episode sweep.
"""
import dataclasses
import os

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.aot import AotCache
from repro.models import registry
from repro.serve import EngineConfig, ServeEngine

EPISODES = int(os.environ.get("ENGINE_FUZZ_EPISODES", "200"))
MAX_SLOTS, MAX_LEN, BS = 3, 48, 8

# The engine modes under test; "slotted" is the parity reference.  The
# preempting pools sit far below the lanes' combined worst case (capacity
# 5 blocks vs 3 lanes x up to 4), so decode growth preempts routinely —
# once with re-prefill-everything resumes, once with the resume riding
# its own published prefix chain.
MODES = {
    "slotted": EngineConfig(max_slots=MAX_SLOTS, max_len=MAX_LEN),
    "paged": EngineConfig(
        max_slots=MAX_SLOTS, max_len=MAX_LEN, kv_layout="paged",
        page_size=BS),
    "paged_chunked": EngineConfig(
        max_slots=MAX_SLOTS, max_len=MAX_LEN, kv_layout="paged",
        page_size=BS, prefill_chunk=BS),
    "prefix": EngineConfig(
        max_slots=MAX_SLOTS, max_len=MAX_LEN, kv_layout="paged",
        page_size=BS, prefix_cache=True),
    "prefix_chunked": EngineConfig(
        max_slots=MAX_SLOTS, max_len=MAX_LEN, kv_layout="paged",
        page_size=BS, prefill_chunk=BS, prefix_cache=True),
    "preempt": EngineConfig(
        max_slots=MAX_SLOTS, max_len=MAX_LEN, kv_layout="paged",
        page_size=BS, num_blocks=6, admission="preempt"),
    "prefix_preempt": EngineConfig(
        max_slots=MAX_SLOTS, max_len=MAX_LEN, kv_layout="paged",
        page_size=BS, num_blocks=6, prefix_cache=True,
        admission="preempt"),
    # everything at once: chunked prefill whose chunks can preempt
    # mid-prompt, prefix hits at chunk offsets, restores amid chunking
    "preempt_chunked": EngineConfig(
        max_slots=MAX_SLOTS, max_len=MAX_LEN, kv_layout="paged",
        page_size=BS, num_blocks=6, prefill_chunk=BS, prefix_cache=True,
        admission="preempt"),
    # host-RAM tier: preempted lanes spill and resume O(copy) instead of
    # replaying — THE gate for the tier being behavior-invisible
    "tiered": EngineConfig(
        max_slots=MAX_SLOTS, max_len=MAX_LEN, kv_layout="paged",
        page_size=BS, num_blocks=6, admission="preempt", host_tier=True),
    # tier + prefix cache + chunking: LRU-evicted chains spill to host
    # and promote back on later matches, amid lane spills and chunked
    # prefills racing the same pool
    "tiered_prefix": EngineConfig(
        max_slots=MAX_SLOTS, max_len=MAX_LEN, kv_layout="paged",
        page_size=BS, num_blocks=6, prefill_chunk=BS, prefix_cache=True,
        admission="preempt", host_tier=True),
}


@pytest.fixture(scope="module")
def setup():
    from repro.launch.mesh import single_device_mesh
    from repro.models.common import ShardRules

    mesh = single_device_mesh()
    rules = ShardRules.for_mesh(mesh)
    # f32 so greedy streams are exactly comparable across engines
    cfg = dataclasses.replace(
        get_smoke_config("smollm-360m"), compute_dtype="float32")
    params = registry.get_module(cfg).init(cfg, jax.random.PRNGKey(0))
    # ONE AotCache across every episode and mode: per-mode executables
    # compile once, then 200 episodes dispatch from cache
    return cfg, mesh, rules, params, AotCache("fuzz")


def make_stream(rng, vocab):
    """Random request stream: (arrival_tick, prompt, budget) triples.

    Prompts mix block-aligned shared prefixes (system prompts — including
    exact-multiple lengths that exercise the COW tail), shared prefixes
    with unique tails, and fully disjoint prompts; bursty arrivals admit
    several requests into one step and quiet gaps drain lanes mid-stream.
    """
    n_prefix = int(rng.integers(1, 3))
    prefixes = [
        rng.integers(0, vocab, int(rng.integers(1, 3)) * BS).astype(np.int32)
        for _ in range(n_prefix)
    ]
    out, tick = [], 0
    for _ in range(int(rng.integers(3, 9))):
        tick += int(rng.integers(0, 4))         # 0 => same-step burst
        r = rng.random()
        if r < 0.25:                            # whole shared prefix (COW)
            prompt = prefixes[int(rng.integers(n_prefix))].copy()
        elif r < 0.7:                           # shared prefix + unique tail
            pre = prefixes[int(rng.integers(n_prefix))]
            tail = rng.integers(0, vocab, int(rng.integers(1, 9)))
            prompt = np.concatenate([pre, tail.astype(np.int32)])
        else:                                   # disjoint prompt
            prompt = rng.integers(
                0, vocab, int(rng.integers(1, 25))).astype(np.int32)
        budget = int(rng.integers(1, 9))
        # keep every request within max_len and the preempt pool's worst case
        prompt = prompt[: MAX_LEN - budget - BS]
        out.append((tick, prompt, budget))
    return out


def drive(cfg, mesh, rules, params, aot, ec, stream, draft_params=None):
    """Replay a stream through one engine; invariants swept every step."""
    eng = ServeEngine(cfg, mesh, rules, params, ec, aot=aot,
                      draft_params=draft_params)
    i, tick, guard = 0, 0, 0
    while i < len(stream) or eng.has_work():
        while i < len(stream) and stream[i][0] <= tick:
            _, prompt, budget = stream[i]
            eng.submit(prompt, max_new_tokens=budget, rid=i)
            i += 1
        eng.step()
        eng.check_invariants()
        tick += 1
        guard += 1
        assert guard < 2000, "engine failed to drain (livelock?)"
    return [list(eng.completions[r].tokens) for r in range(len(stream))], eng


def test_fuzz_cross_engine_parity(setup):
    cfg, mesh, rules, params, aot = setup
    totals = {name: 0 for name in MODES}
    exercised = {"preemptions": 0, "prefix_hit_tokens": 0, "cow_copies": 0,
                 "prefill_chunks": 0, "spills": 0, "restores": 0}
    for seed in range(EPISODES):
        rng = np.random.default_rng(1000 + seed)
        stream = make_stream(rng, cfg.vocab)
        want, _ = drive(cfg, mesh, rules, params, aot, MODES["slotted"], stream)
        for name, ec in MODES.items():
            if name == "slotted":
                continue
            got, eng = drive(cfg, mesh, rules, params, aot, ec, stream)
            assert got == want, (
                f"episode seed={seed}: engine {name!r} diverged from "
                f"slotted greedy output\n  want={want}\n  got ={got}")
            totals[name] += 1
            # every block back home once drained (cached blocks are legal)
            assert eng.alloc.in_use == 0
            assert eng.alloc.num_free + eng.alloc.num_cached \
                == eng.alloc.capacity
            if eng.tier is not None:
                # every lane spill consumed or dropped with its request;
                # host-resident prefix blocks are legal (like cached)
                eng.tier.check()
                assert eng.tier.spilled_lanes == 0
            for k in exercised:
                exercised[k] += eng.counters.get(k, 0)
    # the stream generator must actually exercise the machinery under
    # test, otherwise parity is vacuous (skipped for tiny debug sweeps
    # where a given feature may legitimately never trigger)
    assert exercised["prefill_chunks"] > 0
    if EPISODES >= 20:
        assert exercised["prefix_hit_tokens"] > 0, "no prefix hits at all"
        assert exercised["cow_copies"] > 0, "no COW tails in any episode"
        assert exercised["preemptions"] > 0, "no preemptions in any episode"
        assert exercised["spills"] > 0, "no lane ever spilled to the tier"
        assert exercised["restores"] > 0, "no lane ever restored O(copy)"


# ---------------------------------------------------------------------------
# Recurrent state kinds (engine modes 9 + 10): ssm (xLSTM) + hybrid (Zamba)
# ---------------------------------------------------------------------------
#
# The recurrent families serve on the slotted layout only (no block pool,
# so no pool-pressure preemption) — the adversarial schedule here is
# HOST-INITIATED preemption (`ServeEngine.preempt`), the hook an external
# priority scheduler would use.  The parity reference is the same engine
# without preemptions: resume re-prefills the prompt through the SAME
# bucket executable and replays decode, so parity is bitwise by
# construction — any divergence is a real requeue/replay/zeroing bug.
# Runs a slice of the main episode budget (extra families per episode).
# Zamba runs at both structures: one shared block with attention adapters
# (1.2B) and two alternating blocks over two SSD groups (7B).

REC_EPISODES = max(2, EPISODES // 10)
REC_ARCHS = ("xlstm-1.3b", "zamba2-1.2b", "zamba2-7b")


@pytest.fixture(scope="module", params=REC_ARCHS)
def rec_setup(request):
    from repro.launch.mesh import single_device_mesh
    from repro.models.common import ShardRules

    mesh = single_device_mesh()
    rules = ShardRules.for_mesh(mesh)
    cfg = dataclasses.replace(
        get_smoke_config(request.param), compute_dtype="float32")
    params = registry.get_module(cfg).init(cfg, jax.random.PRNGKey(0))
    return cfg, mesh, rules, params, AotCache(f"fuzz-{cfg.family}")


def drive_recurrent(cfg, mesh, rules, params, aot, stream, preempts,
                    ec=None, draft_params=None):
    """Replay a stream through a slotted recurrent engine; ``preempts``
    maps tick -> slot to preempt (empty = the parity reference).  Sweeps
    the allocator-free invariants plus recurrent evict-time zeroing."""
    eng = ServeEngine(
        cfg, mesh, rules, params,
        ec or EngineConfig(max_slots=MAX_SLOTS, max_len=MAX_LEN), aot=aot,
        draft_params=draft_params)
    i, tick, guard = 0, 0, 0
    while i < len(stream) or eng.has_work():
        while i < len(stream) and stream[i][0] <= tick:
            _, prompt, budget = stream[i]
            eng.submit(prompt, max_new_tokens=budget, rid=i)
            i += 1
        eng.step()
        eng.check_invariants()
        slot = preempts.get(tick)
        if slot is not None and eng.slots[slot] is not None:
            eng.preempt(slot)
        tick += 1
        guard += 1
        assert guard < 2000, "engine failed to drain (livelock?)"
    # drained: every lane free and (checked inside, post-decode) every
    # recurrent leaf exactly zero
    assert all(s is None for s in eng.slots)
    eng.check_invariants()
    return [list(eng.completions[r].tokens) for r in range(len(stream))], eng


def test_fuzz_recurrent_preempt_parity(rec_setup):
    cfg, mesh, rules, params, aot = rec_setup
    preempted = replayed = 0
    for seed in range(REC_EPISODES):
        rng = np.random.default_rng(5000 + seed)
        stream = make_stream(rng, cfg.vocab)
        want, _ = drive_recurrent(
            cfg, mesh, rules, params, aot, stream, {})
        preempts = {
            int(t): int(rng.integers(MAX_SLOTS))
            for t in rng.integers(1, 30, size=int(rng.integers(1, 4)))
        }
        got, eng = drive_recurrent(
            cfg, mesh, rules, params, aot, stream, preempts)
        assert got == want, (
            f"episode seed={seed}: preempted {cfg.family} engine diverged"
            f"\n  want={want}\n  got ={got}")
        preempted += eng.counters["preemptions"]
        replayed += eng.counters["replayed_tokens"]
    # the schedule must actually exercise preempt-and-requeue
    if REC_EPISODES >= 5:
        assert preempted > 0, "no recurrent preemption in any episode"
        assert replayed > 0, "no decode replay in any episode"


# ---------------------------------------------------------------------------
# Speculative decoding: draft/verify engines vs the sequential reference
# ---------------------------------------------------------------------------
#
# Greedy spec decoding must be bitwise-invisible: every committed token is
# the target model's argmax over the committed history (drafts only gate
# how MANY positions commit per round, never WHICH token commits), so a
# spec engine's stream equals the plain slotted engine's stream exactly —
# layered on every state kind and on preempt/spill machinery.  The draft
# is the same architecture with params mixed toward a fresh init: close
# enough to accept routinely, far enough to reject routinely, so both the
# commit and the rollback paths are exercised (vacuity-guarded below).

SPEC_K = 3
SPEC_EPISODES = max(2, EPISODES // 10)
SPEC_REC_EPISODES = max(2, EPISODES // 40)


def _draft_mix(cfg, params, alpha):
    """Draft params: target params mixed ``alpha`` toward a fresh init."""
    noise = registry.get_module(cfg).init(cfg, jax.random.PRNGKey(1))
    return jax.tree.map(lambda a, b: (1 - alpha) * a + alpha * b,
                        params, noise)


def spec_modes(cfg):
    """Spec engine configs (need the draft ArchConfig, hence a function)."""
    sp = {"spec_draft": cfg, "spec_k": SPEC_K}
    return {
        "spec_slotted": EngineConfig(
            max_slots=MAX_SLOTS, max_len=MAX_LEN, **sp),
        # paged + prefix: verify rounds cross block boundaries, publish
        # full blocks, and share chains — with the k-token pre-map
        "spec_prefix": EngineConfig(
            max_slots=MAX_SLOTS, max_len=MAX_LEN, kv_layout="paged",
            page_size=BS, prefill_chunk=BS, prefix_cache=True, **sp),
        # tight pool: decode growth preempts lanes mid-speculation; the
        # resume replays the COMMITTED stream only
        "spec_preempt": EngineConfig(
            max_slots=MAX_SLOTS, max_len=MAX_LEN, kv_layout="paged",
            page_size=BS, num_blocks=6, admission="preempt", **sp),
        # host tier: spec lanes spill O(copy) and resume with the draft
        # cache rebuilt from committed history
        "spec_tiered": EngineConfig(
            max_slots=MAX_SLOTS, max_len=MAX_LEN, kv_layout="paged",
            page_size=BS, num_blocks=6, admission="preempt",
            host_tier=True, **sp),
    }


@pytest.fixture(scope="module")
def spec_setup(setup):
    cfg, mesh, rules, params, aot = setup
    return cfg, mesh, rules, params, _draft_mix(cfg, params, 0.15), aot


def test_fuzz_spec_parity(spec_setup):
    cfg, mesh, rules, params, dparams, aot = spec_setup
    agg = {"spec_accepted": 0, "spec_rejected": 0, "preemptions": 0,
           "spills": 0, "restores": 0}
    for seed in range(SPEC_EPISODES):
        rng = np.random.default_rng(3000 + seed)
        stream = make_stream(rng, cfg.vocab)
        want, _ = drive(cfg, mesh, rules, params, aot, MODES["slotted"],
                        stream)
        for name, ec in spec_modes(cfg).items():
            got, eng = drive(cfg, mesh, rules, params, aot, ec, stream,
                             draft_params=dparams)
            assert got == want, (
                f"episode seed={seed}: spec engine {name!r} diverged from "
                f"the sequential slotted engine\n  want={want}\n  got ={got}")
            if eng.paged:
                assert eng.alloc.in_use == 0
            if eng.tier is not None:
                eng.tier.check()
                assert eng.tier.spilled_lanes == 0
            for k in agg:
                agg[k] += eng.counters.get(k, 0)
    # both halves of the accept rule must fire, or parity is vacuous:
    # accepted == 0 would reduce every round to sequential decode, and
    # rejected == 0 would never exercise KV truncation / state rollback
    assert agg["spec_accepted"] > 0, "no draft token ever accepted"
    assert agg["spec_rejected"] > 0, "no draft token ever rejected"
    if SPEC_EPISODES >= 10:
        assert agg["preemptions"] > 0, "no preemption hit a spec engine"
        assert agg["spills"] > 0, "no spec lane ever spilled to the tier"
        assert agg["restores"] > 0, "no spec lane ever restored O(copy)"


def test_fuzz_spec_recurrent_parity(rec_setup):
    """Spec decoding over the recurrent state kinds (xLSTM ssm state,
    Zamba's hybrid mamba+KV cache): rejection rolls the recurrent leaves
    back via snapshot/where instead of KV truncation, and host-initiated
    preempts land mid-speculation.  Parity reference: the plain
    (non-spec, non-preempt) engine."""
    cfg, mesh, rules, params, aot = rec_setup
    dparams = _draft_mix(cfg, params, 0.02)
    ec = EngineConfig(max_slots=MAX_SLOTS, max_len=MAX_LEN,
                      spec_draft=cfg, spec_k=SPEC_K)
    accepted = rejected = 0
    for seed in range(SPEC_REC_EPISODES):
        rng = np.random.default_rng(7000 + seed)
        stream = make_stream(rng, cfg.vocab)
        want, _ = drive_recurrent(cfg, mesh, rules, params, aot, stream, {})
        preempts = {
            int(t): int(rng.integers(MAX_SLOTS))
            for t in rng.integers(1, 30, size=int(rng.integers(1, 4)))
        }
        got, eng = drive_recurrent(cfg, mesh, rules, params, aot, stream,
                                   preempts, ec=ec, draft_params=dparams)
        assert got == want, (
            f"episode seed={seed}: spec {cfg.family} engine diverged"
            f"\n  want={want}\n  got ={got}")
        accepted += eng.counters["spec_accepted"]
        rejected += eng.counters["spec_rejected"]
    assert accepted > 0, f"no draft token ever accepted ({cfg.family})"
    assert rejected > 0, f"no draft token ever rejected ({cfg.family})"


def test_fuzz_episode_determinism(setup):
    """The same seed replays to the same stream and the same tokens —
    fuzz failures are reproducible by seed number."""
    cfg, mesh, rules, params, aot = setup
    s1 = make_stream(np.random.default_rng(1000), cfg.vocab)
    s2 = make_stream(np.random.default_rng(1000), cfg.vocab)
    assert len(s1) == len(s2)
    assert all(
        a[0] == b[0] and np.array_equal(a[1], b[1]) and a[2] == b[2]
        for a, b in zip(s1, s2)
    )
    a, _ = drive(cfg, mesh, rules, params, aot, MODES["preempt"], s1)
    b, _ = drive(cfg, mesh, rules, params, aot, MODES["preempt"], s2)
    assert a == b


def test_hypothesis_selection():
    """conftest must install the real ``hypothesis`` when the image ships
    it and the ``_minihypothesis`` stand-in only as a fallback."""
    import importlib.metadata

    import hypothesis

    try:
        importlib.metadata.version("hypothesis")
        real_available = True
    except importlib.metadata.PackageNotFoundError:
        real_available = False
    if real_available:
        assert not getattr(hypothesis, "IS_MINI", False)
        assert hypothesis.__name__ == "hypothesis"
    else:
        assert getattr(hypothesis, "IS_MINI", False)


# ---------------------------------------------------------------------------
# Chaos mode: seeded fault schedules across every engine mode
# ---------------------------------------------------------------------------
#
# The robustness contract on top of parity: with a seeded FaultPlan firing
# at every site (corrupted decode fetches, failed prefill dispatches,
# transient alloc failures, lost sched pushes) plus deadlines and
# mid-flight cancels, ``step()`` never raises, the invariant sweep stays
# clean after every step, every request reaches a terminal status, and —
# the bitwise half — every request that ends "ok" is token-for-token the
# fault-free slotted stream, while non-ok requests hold a prefix of it.
# Retries ride the preempt-and-requeue resume path, so a prebuilt engine
# stays at ``steady_builds_delta == 0`` through arbitrary fault schedules.

import json

import jax.numpy as jnp

from repro.serve import FaultPlan

CHAOS_EPISODES = int(os.environ.get("CHAOS_FUZZ_EPISODES", "6"))
CHAOS_RATES = {"decode_logits": 0.05, "prefill": 0.05, "alloc": 0.03,
               "sched_push": 0.05}


class _FakeClock:
    """Deterministic engine clock: one unit per engine step, advanced by
    the driver — deadline expiry becomes a property of the schedule, not
    of host wall-time."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def drive_chaos(cfg, mesh, rules, params, aot, ec, stream, faults,
                deadline_every=0, cancel_ticks=frozenset(),
                draft_params=None):
    """Replay a stream under a seeded fault schedule; invariants swept
    after every step, and the engine must drain without raising."""
    clock = _FakeClock()
    eng = ServeEngine(cfg, mesh, rules, params, ec, aot=aot, faults=faults,
                      clock=clock, draft_params=draft_params)
    i, tick, guard = 0, 0, 0
    while i < len(stream) or eng.has_work():
        while i < len(stream) and stream[i][0] <= tick:
            _, prompt, budget = stream[i]
            kw = {"deadline_s": 40.0} \
                if deadline_every and i % deadline_every == 0 else {}
            eng.submit(prompt, max_new_tokens=budget, rid=i, **kw)
            i += 1
        if tick in cancel_ticks and eng.live:
            # prefer rids sitting in a race window — queued resumes
            # (between requeue and re-admission) and lanes still
            # replaying their pre-preemption tokens — so cancel lands
            # in the states where refund bugs would actually hide
            resumes = sorted(r.rid for r in eng.queue if r.resume)
            replaying = sorted(s.rid for s in eng.slots
                               if s is not None and s.generated < s.emit_from)
            pool = resumes or replaying or sorted(eng.live)
            eng.cancel(pool[len(pool) // 2])
        eng.step()
        eng.check_invariants()
        clock.t += 1.0
        tick += 1
        guard += 1
        assert guard < 3000, "engine failed to drain under chaos"
    assert not eng.live and not eng.queue
    return eng


def test_chaos_fuzz(setup):
    cfg, mesh, rules, params, aot = setup
    # prebuild every mode's executables: retries and resumes must then
    # dispatch purely from cache (steady_builds_delta == 0 under faults)
    for ec in MODES.values():
        ServeEngine(cfg, mesh, rules, params, ec, aot=aot).prebuild()
    builds0 = aot.stats["builds"]
    agg = {"faults_injected": 0, "faults_detected": 0, "retries": 0,
           "status_ok": 0, "status_timeout": 0, "status_cancelled": 0,
           "status_failed": 0}
    recovered = 0
    for seed in range(CHAOS_EPISODES):
        rng = np.random.default_rng(9000 + seed)
        stream = make_stream(rng, cfg.vocab)
        want, _ = drive(cfg, mesh, rules, params, aot,
                        MODES["slotted"], stream)
        for mi, (name, ec) in enumerate(MODES.items()):
            faults = FaultPlan(seed * len(MODES) + mi, CHAOS_RATES)
            cancel_ticks = {int(t) for t in rng.integers(1, 25, size=2)}
            eng = drive_chaos(cfg, mesh, rules, params, aot, ec, stream,
                              faults, deadline_every=3,
                              cancel_ticks=cancel_ticks)
            for rid in range(len(stream)):
                c = eng.completions[rid]
                assert c.status in ("ok", "timeout", "cancelled", "failed")
                got = list(c.tokens)
                if c.status == "ok":
                    # fault-touched or not: an "ok" request is bitwise
                    # the fault-free stream (retries replay exactly)
                    assert got == want[rid], (
                        f"seed={seed} mode={name} rid={rid}: ok request "
                        f"diverged\n  want={want[rid]}\n  got ={got}")
                else:
                    assert got == want[rid][: len(got)], (
                        f"seed={seed} mode={name} rid={rid}: "
                        f"{c.status} request is not a prefix of the "
                        f"fault-free stream")
            if eng.paged:
                assert eng.alloc.in_use == 0
            st = eng.stats
            for k in agg:
                agg[k] += st[k]
        # faults alone (no deadlines, no cancels) under a generous retry
        # budget: every request recovers to "ok", bitwise
        eng = drive_chaos(cfg, mesh, rules, params, aot,
                          dataclasses.replace(MODES["preempt"], max_retries=8),
                          stream, FaultPlan(10_000 + seed, CHAOS_RATES))
        for rid in range(len(stream)):
            c = eng.completions[rid]
            assert c.status == "ok" and list(c.tokens) == want[rid], (
                f"seed={seed} rid={rid}: faults-only request ended "
                f"{c.status!r} ({c.error}) or diverged")
        recovered += eng.stats["retries"]
    assert aot.stats["builds"] == builds0, (
        "chaos retries forced fresh compiles — the retry path must reuse "
        "prebuilt executables")
    # the schedule must actually exercise the machinery (vacuity guard)
    assert agg["faults_injected"] > 0, "no faults fired at all"
    assert agg["faults_detected"] > 0, "no sentinel ever detected"
    assert agg["retries"] > 0, "no lane ever retried"
    assert agg["status_ok"] > 0
    assert recovered > 0, "no faults-only request ever retried"
    if CHAOS_EPISODES >= 4:
        assert agg["status_cancelled"] > 0, "no cancel landed"


def test_chaos_retry_exhaustion_is_structured_failure(setup):
    """A lane that faults on every retry goes terminal with status
    "failed" (data, not an exception), after exactly max_retries + 1
    attempts."""
    cfg, mesh, rules, params, aot = setup
    faults = FaultPlan(1, {"prefill": 1.0})
    eng = ServeEngine(cfg, mesh, rules, params, MODES["slotted"], aot=aot,
                      faults=faults)
    rid = eng.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=4)
    guard = 0
    while eng.has_work():
        eng.step()
        eng.check_invariants()
        guard += 1
        assert guard < 100
    c = eng.completions[rid]
    assert c.status == "failed"
    assert c.retries == eng.econ.max_retries + 1
    assert "prefill" in c.error
    assert c.tokens == []
    assert eng.counters["status_failed"] == 1


def test_genuine_nonfinite_logits_detected(setup):
    """Not an injected sentinel: NaN-poisoned weights make the device
    itself produce non-finite logits, the fused program reports the
    sentinel through the ordinary token fetch, and the engine fails the
    request cleanly instead of emitting garbage or raising."""
    cfg, mesh, rules, params, aot = setup
    badp = jax.tree.map(lambda x: jnp.full_like(x, jnp.nan), params)
    eng = ServeEngine(cfg, mesh, rules, badp, MODES["paged"], aot=aot)
    rid = eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
    guard = 0
    while eng.has_work():
        eng.step()
        eng.check_invariants()
        guard += 1
        assert guard < 100
    c = eng.completions[rid]
    assert c.status == "failed"
    assert "non-finite" in c.error
    assert eng.counters["faults_detected"] > 0
    assert eng.counters["faults_injected"] == 0   # no plan: all genuine
    assert eng.alloc.in_use == 0                  # refs fully refunded


def test_genuine_nonfinite_mid_decode(setup):
    """Weights poisoned AFTER the first token: the prompt prefills
    cleanly, then decode hits non-finite logits mid-stream — the tokens
    emitted before the fault survive on the failed completion."""
    cfg, mesh, rules, params, aot = setup
    eng = ServeEngine(cfg, mesh, rules, params, MODES["slotted"], aot=aot)
    rid = eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=6)
    eng.step()                           # prefill + first decode
    emitted = len(eng.live[rid].tokens)
    assert emitted >= 1
    eng.params = jax.device_put(
        jax.tree.map(lambda x: jnp.full_like(x, jnp.nan), eng.params),
        eng._p_sh)
    guard = 0
    while eng.has_work():
        eng.step()
        eng.check_invariants()
        guard += 1
        assert guard < 100
    c = eng.completions[rid]
    assert c.status == "failed"
    assert "decode" in c.error or "prefill" in c.error
    assert len(c.tokens) >= emitted               # pre-fault emissions kept
    assert eng.counters["faults_detected"] > 0


def test_chaos_snapshot_kill_restore(setup):
    """Kill-and-restore mid-episode: snapshot the engine's host truth at
    an arbitrary step, rebuild a FRESH engine from the (JSON round-
    tripped) snapshot, finish the stream there — bitwise identical to the
    uninterrupted run, with no new executable builds."""
    cfg, mesh, rules, params, aot = setup
    for name in ("slotted", "paged_chunked", "prefix_preempt"):
        ec = MODES[name]
        stream = make_stream(np.random.default_rng(777), cfg.vocab)
        want, _ = drive(cfg, mesh, rules, params, aot,
                        MODES["slotted"], stream)
        for kill_tick in (1, 3, 6):
            eng = ServeEngine(cfg, mesh, rules, params, ec, aot=aot)
            eng.prebuild()
            builds0 = aot.stats["builds"]
            i, tick = 0, 0
            while tick < kill_tick and (i < len(stream) or eng.has_work()):
                while i < len(stream) and stream[i][0] <= tick:
                    _, prompt, budget = stream[i]
                    eng.submit(prompt, max_new_tokens=budget, rid=i)
                    i += 1
                eng.step()
                tick += 1
            # crash boundary: only what snapshot() serialized survives
            snap = json.loads(json.dumps(eng.snapshot()))
            del eng
            eng2 = ServeEngine(cfg, mesh, rules, params, ec, aot=aot)
            eng2.restore(snap)
            guard = 0
            while i < len(stream) or eng2.has_work():
                while i < len(stream) and stream[i][0] <= tick:
                    _, prompt, budget = stream[i]
                    eng2.submit(prompt, max_new_tokens=budget, rid=i)
                    i += 1
                eng2.step()
                eng2.check_invariants()
                tick += 1
                guard += 1
                assert guard < 2000
            got = [list(eng2.completions[r].tokens)
                   for r in range(len(stream))]
            assert got == want, (
                f"mode={name} kill_tick={kill_tick}: restored engine "
                f"diverged\n  want={want}\n  got ={got}")
            assert all(c.status == "ok"
                       for c in eng2.completions.values())
            assert eng2.counters["snapshot_restores"] == 1
            assert aot.stats["builds"] == builds0, (
                "restore forced fresh compiles")
