"""Host-RAM KV/state tiering: spill/restore correctness and conservation.

The tier's contract has three legs, each tested here:

1. **O(copy) resume is behavior-invisible** — a lane restored from its
   host spill continues bitwise-identically to a never-preempted run,
   with zero replay decode steps for the covered tokens (the payload IS
   the evicted state, so this is exact, not approximate).
2. **Four-state conservation** — ``free + live + cached + spilled ==
   capacity`` holds across the device pool and the host tier after
   every step of arbitrary preempt/hold/park/release schedules
   (:func:`repro.serve.paged.check_tiered`, swept by the engine's own
   ``check_invariants``), and every chain key has exactly one owner.
3. **Graceful refusal** — a bounded tier that cannot make room drops
   the spill and the resume falls back to decode replay; correctness
   never depends on host capacity.

The schedules are drawn through ``hypothesis`` (the image's real
package when present, ``tests/_minihypothesis.py`` otherwise — see
``test_engine_fuzz.test_hypothesis_selection``) across four layouts:
slotted KV, paged with preemption, paged with a bounded tier + prefix
cache, and slotted recurrent (xLSTM) where hold() must snapshot
immediately because the decode freeze zeroes inactive lanes' state.
"""
import dataclasses

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_smoke_config
from repro.core.aot import AotCache
from repro.models import registry
from repro.serve import EngineConfig, HostTier, LaneSpill, ServeEngine

MAX_SLOTS, MAX_LEN, BS = 3, 48, 8

LAYOUTS = {
    "slotted": EngineConfig(max_slots=MAX_SLOTS, max_len=MAX_LEN,
                            host_tier=True),
    "paged": EngineConfig(
        max_slots=MAX_SLOTS, max_len=MAX_LEN, kv_layout="paged",
        page_size=BS, num_blocks=6, admission="preempt", host_tier=True),
    # bounded tier + prefix cache: lane spills compete with spilled
    # chains for 8 block units, so refusals/drops fire and resumes must
    # fall back to replay without losing parity
    "bounded_prefix": EngineConfig(
        max_slots=MAX_SLOTS, max_len=MAX_LEN, kv_layout="paged",
        page_size=BS, num_blocks=6, prefix_cache=True,
        admission="preempt", host_tier=True, host_tier_blocks=8),
    "recurrent": EngineConfig(max_slots=MAX_SLOTS, max_len=MAX_LEN,
                              host_tier=True, park_idle_s=4.0),
}
# the parity reference per layout: same engine family, no tier, no
# schedule interference
REFS = {
    "slotted": EngineConfig(max_slots=MAX_SLOTS, max_len=MAX_LEN),
    "paged": EngineConfig(max_slots=MAX_SLOTS, max_len=MAX_LEN),
    "bounded_prefix": EngineConfig(max_slots=MAX_SLOTS, max_len=MAX_LEN),
    "recurrent": EngineConfig(max_slots=MAX_SLOTS, max_len=MAX_LEN),
}
ARCH = {"slotted": "smollm-360m", "paged": "smollm-360m",
        "bounded_prefix": "smollm-360m", "recurrent": "xlstm-1.3b"}


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture(scope="module")
def setups():
    from repro.launch.mesh import single_device_mesh
    from repro.models.common import ShardRules

    mesh = single_device_mesh()
    rules = ShardRules.for_mesh(mesh)
    out = {}
    for arch in sorted(set(ARCH.values())):
        cfg = dataclasses.replace(
            get_smoke_config(arch), compute_dtype="float32")
        params = registry.get_module(cfg).init(cfg, jax.random.PRNGKey(0))
        out[arch] = (cfg, mesh, rules, params, AotCache(f"tier-{arch}"))
    return out


def make_stream(rng, vocab):
    out, tick = [], 0
    for _ in range(int(rng.integers(3, 7))):
        tick += int(rng.integers(0, 3))
        plen = int(rng.integers(2, 20))
        prompt = rng.integers(0, vocab, plen).astype(np.int32)
        budget = int(rng.integers(2, 8))
        out.append((tick, prompt[: MAX_LEN - budget - BS], budget))
    return out


def drive_ref(setups, layout, stream):
    cfg, mesh, rules, params, aot = setups[ARCH[layout]]
    eng = ServeEngine(cfg, mesh, rules, params, REFS[layout], aot=aot)
    guard, i, tick = 0, 0, 0
    while i < len(stream) or eng.has_work():
        while i < len(stream) and stream[i][0] <= tick:
            _, prompt, budget = stream[i]
            eng.submit(prompt, max_new_tokens=budget, rid=i)
            i += 1
        eng.step()
        tick += 1
        guard += 1
        assert guard < 2000
    return [list(eng.completions[r].tokens) for r in range(len(stream))]


def apply_op(eng, op, rng):
    """One schedule op against a live engine; silently skips when the
    op's precondition doesn't hold (no decoding lane to preempt, nothing
    held, ...) — the schedule is adversarial, not scripted."""
    decoding = [i for i, s in enumerate(eng.slots)
                if s is not None and s.prefilled >= s.plen
                and s.generated >= 1 and not s.held]
    held = [s.rid for s in eng.slots if s is not None and s.held]
    if op == "preempt" and decoding:
        eng.preempt(int(rng.choice(decoding)))
    elif op == "hold" and decoding:
        eng.hold(eng.slots[int(rng.choice(decoding))].rid)
    elif op == "release":
        pool = held + sorted(eng.parked)
        if pool:
            eng.release(int(rng.choice(pool)))
    elif op == "idle":
        # long-idle: the park sweep (when configured) moves held lanes
        # off-HBM on the next step
        eng.clock.t += 5.0


@settings(max_examples=4, deadline=None)
@given(layout=st.sampled_from(sorted(LAYOUTS)),
       seed=st.integers(0, 10_000),
       ops=st.lists(st.sampled_from(
           ["step", "step", "preempt", "hold", "release", "idle"]),
           min_size=6, max_size=20))
def test_spill_restore_schedules(setups, layout, seed, ops):
    """Random preempt/hold/park/release schedules across every layout:
    conservation after every step, bitwise token parity at the end."""
    cfg, mesh, rules, params, aot = setups[ARCH[layout]]
    rng = np.random.default_rng(seed)
    stream = make_stream(rng, cfg.vocab)
    want = drive_ref(setups, layout, stream)
    clock = _FakeClock()
    eng = ServeEngine(cfg, mesh, rules, params, LAYOUTS[layout], aot=aot,
                      clock=clock)
    i, tick, guard = 0, 0, 0
    schedule = list(ops)
    while i < len(stream) or eng.has_work():
        while i < len(stream) and stream[i][0] <= tick:
            _, prompt, budget = stream[i]
            eng.submit(prompt, max_new_tokens=budget, rid=i)
            i += 1
        if schedule:
            op = schedule.pop()
            if op != "step":
                apply_op(eng, op, rng)
        elif any(s is not None and s.held for s in eng.slots) or eng.parked:
            # schedule exhausted: release everything so the drain ends
            for s in list(eng.slots):
                if s is not None and s.held:
                    eng.release(s.rid)
            for rid in sorted(eng.parked):
                eng.release(rid)
        eng.step()
        eng.check_invariants()      # includes check_tiered + tier.check
        clock.t += 1.0
        tick += 1
        guard += 1
        assert guard < 2000, "tiered engine failed to drain"
    got = [list(eng.completions[r].tokens) for r in range(len(stream))]
    assert got == want, (
        f"layout={layout} seed={seed} ops={ops}: tiered schedule "
        f"diverged\n  want={want}\n  got ={got}")
    assert eng.tier.spilled_lanes == 0      # every spill consumed/dropped
    assert all(c.status == "ok" for c in eng.completions.values())


# ---------------------------------------------------------------------------
# Targeted lifecycle: hold / park / release
# ---------------------------------------------------------------------------


def _drive(eng, stream, clock=None, hook=None):
    i, tick, guard = 0, 0, 0
    while i < len(stream) or eng.has_work():
        while i < len(stream) and stream[i][0] <= tick:
            _, prompt, budget = stream[i]
            eng.submit(prompt, max_new_tokens=budget, rid=i)
            i += 1
        if hook is not None:
            hook(eng, tick)
        eng.step()
        eng.check_invariants()
        if clock is not None:
            clock.t += 1.0
        tick += 1
        guard += 1
        assert guard < 2000
    return [list(eng.completions[r].tokens) for r in range(len(stream))]


def test_park_is_o_copy_not_replay(setups):
    """A lane held past park_idle_s parks off-HBM (its slot frees), and
    release restores it from the tier with ZERO replayed tokens — the
    resume is O(bytes copied), not O(generated)."""
    cfg, mesh, rules, params, aot = setups["smollm-360m"]
    ec = dataclasses.replace(LAYOUTS["paged"], park_idle_s=4.0)
    stream = [(0, np.arange(1, 13, dtype=np.int32), 8)]
    want = drive_ref(setups, "paged", stream)
    clock = _FakeClock()
    eng = ServeEngine(cfg, mesh, rules, params, ec, aot=aot, clock=clock)
    state = {"parked": False}

    def hook(eng, tick):
        if tick == 2:
            assert eng.hold(0)
            clock.t += 10.0                 # idle past the threshold
        if eng.parked and not state["parked"]:
            state["parked"] = True
            assert all(s is None for s in eng.slots)    # slot reclaimed
            eng.release(0)

    got = _drive(eng, stream, clock=clock, hook=hook)
    assert got == want
    assert state["parked"], "the park sweep never fired"
    assert eng.counters["parked"] == 1
    assert eng.counters["spills"] >= 1
    assert eng.counters["restores"] >= 1
    assert eng.counters["replayed_tokens"] == 0, (
        "a parked lane's resume replayed decode steps — the restore "
        "must be O(copy)")
    assert eng.counters["preemptions"] == 0     # park is not a preempt


def test_hold_release_kv_keeps_device_state(setups):
    """A held KV lane stays device-resident: release flips the active
    bit back with no restore, no replay, and the stream is bitwise the
    uninterrupted one."""
    cfg, mesh, rules, params, aot = setups["smollm-360m"]
    stream = [(0, np.arange(1, 10, dtype=np.int32), 6)]
    want = drive_ref(setups, "slotted", stream)
    eng = ServeEngine(cfg, mesh, rules, params, LAYOUTS["slotted"], aot=aot)

    def hook(eng, tick):
        if tick == 2:
            eng.hold(0)
        if tick == 5:
            eng.release(0)

    got = _drive(eng, stream, hook=hook)
    assert got == want
    assert eng.counters["holds"] == 1 and eng.counters["releases"] == 1
    assert eng.counters["restores"] == 0        # KV hold: state never left
    # held ticks made no progress on the lane
    assert eng.counters["replayed_tokens"] == 0


def test_hold_recurrent_spills_immediately(setups):
    """Recurrent lanes CANNOT be held in place — the decode freeze
    zeroes inactive lanes' recurrent leaves — so hold() snapshots to the
    tier at hold time and release restores it; parity is bitwise."""
    cfg, mesh, rules, params, aot = setups["xlstm-1.3b"]
    stream = [(0, np.arange(1, 10, dtype=np.int32), 6),
              (1, np.arange(3, 14, dtype=np.int32), 5)]
    want = drive_ref(setups, "recurrent", stream)
    clock = _FakeClock()
    eng = ServeEngine(cfg, mesh, rules, params, LAYOUTS["recurrent"],
                      aot=aot, clock=clock)

    def hook(eng, tick):
        if tick == 2 and eng.slots[0] is not None:
            eng.hold(eng.slots[0].rid)
            assert eng.counters["spills"] == 1, (
                "recurrent hold must spill at hold() time — the device "
                "copy is zeroed by the next decode's freeze")
        if tick == 4 and eng.slots[0] is not None and eng.slots[0].held:
            eng.release(eng.slots[0].rid)

    got = _drive(eng, stream, clock=clock, hook=hook)
    assert got == want
    assert eng.counters["restores"] >= 1
    assert eng.counters["replayed_tokens"] == 0


def test_hold_recurrent_without_tier_raises(setups):
    cfg, mesh, rules, params, aot = setups["xlstm-1.3b"]
    eng = ServeEngine(cfg, mesh, rules, params, REFS["recurrent"], aot=aot)
    eng.submit(np.arange(1, 8, dtype=np.int32), max_new_tokens=4)
    eng.step()
    with pytest.raises(ValueError, match="host tier"):
        eng.hold(0)
    while eng.has_work():
        eng.step()


def test_preempted_lane_restores_without_replay(setups):
    """THE tentpole property, stated directly: preempt a mid-decode
    paged lane, and its resume must restore O(copy) — zero replayed
    decode tokens, zero re-prefilled chunks for covered positions —
    yet produce the bitwise-identical stream."""
    cfg, mesh, rules, params, aot = setups["smollm-360m"]
    stream = [(0, np.arange(1, 13, dtype=np.int32), 8)]
    want = drive_ref(setups, "paged", stream)
    eng = ServeEngine(cfg, mesh, rules, params, LAYOUTS["paged"], aot=aot)

    def hook(eng, tick):
        if tick == 3 and eng.slots[0] is not None \
                and eng.slots[0].generated >= 2:
            eng.preempt(0)

    got = _drive(eng, stream, hook=hook)
    assert got == want
    assert eng.counters["preemptions"] == 1
    assert eng.counters["spills"] == 1 and eng.counters["restores"] == 1
    assert eng.counters["replayed_tokens"] == 0
    assert eng.counters["restored_bytes"] > 0
    # the prompt prefilled once: the resume re-prefilled nothing
    assert eng.counters["prefill_tokens"] == 12


def test_slotted_lane_spill_round_trips_position_minor(setups):
    """A slotted lane's spill is its (L, Hk, dh, S) slice of each KV leaf,
    position minor-most; preempted mid-decode and restored from it, the
    lane finishes the never-preempted stream with nothing replayed, and
    its prompt's KV on the device is the payload's."""
    cfg, mesh, rules, params, aot = setups["smollm-360m"]
    stream = [(0, np.arange(1, 13, dtype=np.int32), 8)]
    want = drive_ref(setups, "slotted", stream)
    eng = ServeEngine(cfg, mesh, rules, params, LAYOUTS["slotted"], aot=aot)
    spills = []

    def capture(slot, _capture=eng._capture_spill):
        spills.append(_capture(slot))
        return spills[-1]

    eng._capture_spill = capture

    def hook(eng, tick):
        if tick == 3 and eng.slots[0] is not None \
                and eng.slots[0].generated >= 2:
            eng.preempt(0)

    assert _drive(eng, stream, hook=hook) == want
    assert eng.counters["spills"] == 1 and eng.counters["restores"] == 1
    assert eng.counters["replayed_tokens"] == 0
    (sp,) = spills
    shape = (cfg.n_layers, cfg.n_kv, cfg.head_dim, MAX_LEN)
    assert sp.kind == "lane"
    assert {k: v.shape for k, v in sp.leaves.items()} == {"k": shape,
                                                          "v": shape}
    plen = stream[0][1].size
    for name, leaf in sp.leaves.items():
        lane = np.asarray(eng.state["cache"][name])[:, 0]
        np.testing.assert_array_equal(lane[..., :plen], leaf[..., :plen])


def test_host_tier_second_level_prefix_cache(setups):
    """LRU-reclaimed prefix chains spill to host and later admissions
    promote them back — the prompt's prefill is skipped even though the
    device index lost the chain."""
    cfg, mesh, rules, params, aot = setups["smollm-360m"]
    ec = EngineConfig(
        max_slots=2, max_len=MAX_LEN, kv_layout="paged", page_size=BS,
        num_blocks=8, prefix_cache=True, host_tier=True)
    eng = ServeEngine(cfg, mesh, rules, params, ec, aot=aot)
    sys_prompt = np.arange(1, 17, dtype=np.int32)       # 2 full blocks

    def run(prompt, rid):
        eng.submit(prompt, max_new_tokens=4, rid=rid)
        guard = 0
        while eng.has_work():
            eng.step()
            eng.check_invariants()
            guard += 1
            assert guard < 200
        return list(eng.completions[rid].tokens)

    first = run(sys_prompt, 0)
    assert eng.alloc.num_cached > 0
    # churn the pool with disjoint prompts until the chain is reclaimed;
    # the on_evict hook spills each block to the tier as it dies
    rid = 1
    rng = np.random.default_rng(7)
    while eng.counters["prefix_spills"] == 0:
        run(rng.integers(100, cfg.vocab, 24).astype(np.int32), rid)
        rid += 1
        assert rid < 20, "pool churn never evicted the cached chain"
    assert eng.tier.spilled_blocks > 0
    # the same system prompt again: the device index misses, the host
    # tier promotes, and the covered positions skip prefill
    hits0 = eng.counters["prefix_hit_tokens"]
    again = run(sys_prompt, rid)
    assert again == first
    assert eng.counters["host_prefix_hits"] > 0, (
        "the spilled chain was never promoted from the host tier")
    assert eng.counters["prefix_hit_tokens"] > hits0


# ---------------------------------------------------------------------------
# HostTier unit behavior
# ---------------------------------------------------------------------------


def _lane(rid, nblocks=0, leaves=None, generated=1, prefilled=4):
    if leaves is not None:
        return LaneSpill(rid, "lane", prefilled, generated, leaves=leaves)
    blocks = [{"k": np.zeros((2, BS), np.float32)} for _ in range(nblocks)]
    return LaneSpill(rid, "paged", prefilled, generated, blocks=blocks)


def test_tier_bounded_budget_and_lru():
    tier = HostTier(capacity_blocks=3)
    pay = lambda: {"k": np.ones((2, BS), np.float32)}
    assert tier.put_block(b"a", pay()) and tier.put_block(b"b", pay())
    assert tier.put_block(b"c", pay())
    tier.check()
    assert tier.host_free == 0
    # a fourth block LRU-drops the oldest ("a"), never a lane spill
    assert tier.put_block(b"d", pay())
    assert not tier.has_block(b"a") and tier.has_block(b"d")
    assert tier.drops == 1
    # lane spills pin their units: a 3-block lane evicts every prefix
    # block; a 4-block lane cannot fit and is refused
    assert tier.put_lane(_lane(1, nblocks=3))
    assert tier.spilled_blocks == 3 and len(tier._prefix) == 0
    assert not tier.put_lane(_lane(2, nblocks=4))
    assert not tier.has_lane(2)
    tier.check()
    # whole-lane snapshots are outside the block budget
    assert tier.put_lane(_lane(3, leaves={"h": np.zeros(4, np.float32)}))
    tier.check()


def test_tier_match_chain_and_move_semantics():
    tier = HostTier()
    pay = lambda: {"k": np.ones(4, np.float32)}
    for key in (b"k0", b"k1", b"k2"):
        assert tier.put_block(key, pay())
    assert tier.match_chain([b"k0", b"k1", b"k2", b"k3"]) == 3
    assert tier.match_chain([b"k0", b"k1", b"k2"], start=1) == 2
    assert tier.match_chain([b"kX", b"k1"]) == 0
    # pop is a move: the key leaves the tier (device owns it now)
    assert tier.pop_block(b"k1") is not None
    assert not tier.has_block(b"k1")
    assert tier.match_chain([b"k0", b"k1"]) == 1
    # discard drops without counting a hit (republished on device)
    hits = tier.prefix_hits
    tier.discard_block(b"k2")
    assert not tier.has_block(b"k2") and tier.prefix_hits == hits
    tier.check()
    assert tier.used_bytes == 16    # only k0's payload remains


def test_tier_stale_lane_replaced():
    tier = HostTier()
    assert tier.put_lane(_lane(7, nblocks=1, generated=2))
    assert tier.put_lane(_lane(7, nblocks=2, generated=5))
    sp = tier.pop_lane(7)
    assert sp.generated == 5 and len(sp.blocks) == 2
    assert tier.pop_lane(7) is None
    tier.check()
    assert tier.used_bytes == 0


# ---------------------------------------------------------------------------
# Exact byte accounting (the integer-division truncation fix)
# ---------------------------------------------------------------------------


def test_exact_share_no_truncation():
    from repro.serve.engine import _exact_share

    # prime denominators: the old ``total // denom * units`` form loses
    # up to denom-1 bytes per unit; multiply-before-divide is exact at
    # the boundary and never over-counts
    for total, denom in ((1_000_003, 7), (12_345_679, 13), (997, 31)):
        assert _exact_share(total, denom, denom) == total
        assert _exact_share(total, 0, denom) == 0
        running = [_exact_share(total, u, denom) for u in range(denom + 1)]
        assert running == sorted(running)           # monotone in units
        assert all(v <= total for v in running)
        # the truncating form visibly under-counts on these totals
        assert any(_exact_share(total, u, denom) > u * (total // denom)
                   for u in range(denom + 1))


def test_kv_gauge_exact_with_prime_block_count(setups):
    """With a prime block count the per-block byte share is fractional;
    the gauge must report the exact multiply-before-divide value, not
    ``peak * (total // num_blocks)`` (which loses up to
    ``num_blocks - 1`` bytes per block counted)."""
    from repro.serve.engine import _exact_share

    cfg, mesh, rules, params, aot = setups["smollm-360m"]
    ec = EngineConfig(max_slots=2, max_len=MAX_LEN, kv_layout="paged",
                      page_size=BS, num_blocks=7)     # prime
    eng = ServeEngine(cfg, mesh, rules, params, ec, aot=aot)
    blocks = [eng.alloc.alloc() for _ in range(eng.alloc.num_free)]
    assert eng.alloc.peak_in_use == eng.alloc.capacity
    eng._note_kv_usage()
    want = _exact_share(eng.kv_reserved_bytes, eng.alloc.capacity,
                        eng._num_blocks)
    assert eng.obs.metrics.gauge("kv_peak_used_bytes").value == want
    # the exact form is tight: a full pool is within one block share of
    # the whole reservation, which the truncating form cannot guarantee
    # for totals the block count does not divide
    assert eng.kv_reserved_bytes - want \
        <= -(-eng.kv_reserved_bytes // eng._num_blocks)
    for b in blocks:
        eng.alloc.free(b)
