"""Paged KV-cache subsystem: block-allocator property tests (hypothesis /
the _minihypothesis stand-in), block-table compaction invariants, paged
gather/scatter plumbing, the Pallas paged-decode kernel vs its oracle,
and THE layout-parity property — the paged engine (whole-bucket and
chunked prefill) must match the slotted engine token-for-token under
greedy decoding on a staggered-arrival trace."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_smoke_config
from repro.models import registry
from repro.serve import BlockAllocator, EngineConfig, ServeEngine, SlotTables, blocks_for
from repro.serve.paged import NULL_BLOCK


@pytest.fixture(scope="module")
def setup():
    from repro.launch.mesh import single_device_mesh
    from repro.models.common import ShardRules

    mesh = single_device_mesh()
    rules = ShardRules.for_mesh(mesh)
    # f32 so greedy comparisons against the slotted engine are exact
    cfg = dataclasses.replace(
        get_smoke_config("smollm-360m"), compute_dtype="float32")
    params = registry.get_module(cfg).init(cfg, jax.random.PRNGKey(0))
    return cfg, mesh, rules, params


def _prompts(cfg, rng, lens):
    return [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lens]


# ---------------------------------------------------------------------------
# Block allocator: property tests
# ---------------------------------------------------------------------------


@settings(max_examples=25)
@given(ops=st.lists(st.integers(min_value=0, max_value=9), min_size=0,
                    max_size=60))
def test_allocator_roundtrip_invariants(ops):
    """Random alloc/free walks: ids stay unique, the null block is never
    handed out, free+in_use always partitions the pool, and freed blocks
    become allocatable again."""
    alloc = BlockAllocator(num_blocks=8, block_size=4)
    held: list[int] = []
    for op in ops:
        if op < 6 and alloc.num_free:          # bias towards allocating
            b = alloc.alloc()
            assert b != NULL_BLOCK
            assert b not in held               # no double assignment
            held.append(b)
        elif held:
            alloc.free(held.pop(0))
        alloc.check()
        assert alloc.in_use == len(held)
    for b in held:
        alloc.free(b)
    alloc.check()
    assert alloc.num_free == alloc.capacity
    assert alloc.peak_in_use <= alloc.capacity


@settings(max_examples=25)
@given(ops=st.lists(st.integers(min_value=0, max_value=99), min_size=0,
                    max_size=100))
def test_refcount_allocator_interleavings(ops):
    """Arbitrary interleavings of alloc / share / free / preempt (bulk
    free) / publish / lookup: no double-free, no leak, no block freed
    while references live, and prefix-index lookups never return a block
    that sits on the free list."""
    alloc = BlockAllocator(num_blocks=9, block_size=4)
    refs: dict[int, int] = {}                  # shadow refcounts
    published: dict[int, bytes] = {}
    next_key = [0]
    for op in ops:
        kind = op % 6
        if kind in (0, 1):                      # alloc (bias)
            if alloc.available:
                b = alloc.alloc()
                assert refs.get(b, 0) == 0, "double assignment"
                refs[b] = 1
                published.pop(b, None)          # reclaimed cached block
        elif kind == 2 and refs:                # share a live block
            b = sorted(refs)[op % len(refs)]
            alloc.share(b)
            refs[b] += 1
        elif kind == 3 and refs:                # free one reference
            b = sorted(refs)[op % len(refs)]
            alloc.free(b)
            refs[b] -= 1
            if not refs[b]:
                del refs[b]
        elif kind == 4 and refs:                # publish a live block
            b = sorted(refs)[op % len(refs)]
            key = next_key[0].to_bytes(4, "big")
            next_key[0] += 1
            if alloc.publish(b, key):
                published[b] = key
        elif kind == 5 and refs:                # preempt: bulk release
            for b in list(refs):
                for _ in range(refs[b]):
                    alloc.free(b)
            refs.clear()
        alloc.check()
        assert alloc.in_use == len(refs)
        for b, n in refs.items():
            assert alloc.refcount(b) == n
        # a published key either resolves to a live/cached block or was
        # evicted — never to a block on the free list
        for b, key in list(published.items()):
            got = alloc.lookup([key])
            if not got:
                del published[b]                # evicted or superseded
                continue
            assert got == [b]
            assert alloc.refcount(b) >= 1 or alloc.num_cached > 0
    for b in list(refs):
        for _ in range(refs.pop(b)):
            alloc.free(b)
    alloc.check()
    assert alloc.in_use == 0
    assert alloc.num_free + alloc.num_cached == alloc.capacity


def test_refcount_share_and_cached_lifecycle():
    """share() stacks references; free() only releases at refcount 0;
    published blocks park in the cached set instead of the free list and
    revive on the next hit; the LRU cached block is reclaimed when the
    free list runs dry."""
    alloc = BlockAllocator(num_blocks=4, block_size=2)
    a = alloc.alloc()
    alloc.share(a)
    alloc.free(a)
    assert alloc.refcount(a) == 1 and alloc.in_use == 1   # still live
    with pytest.raises(ValueError):
        alloc.share(99)
    assert alloc.publish(a, b"ka")
    assert not alloc.publish(a, b"kb")                    # one key per block
    alloc.free(a)
    assert alloc.in_use == 0 and alloc.num_cached == 1    # parked, not freed
    assert alloc.lookup([b"ka"]) == [a]
    alloc.share(a)                                        # revive from cache
    assert alloc.refcount(a) == 1 and alloc.num_cached == 0
    alloc.free(a)

    # exhaust the free list: the LRU cached block gets reclaimed and its
    # index entry dropped
    b = alloc.alloc()
    c = alloc.alloc()
    assert {b, c} == {2, 3}    # cached block a skipped while free ids remain
    d = alloc.alloc()          # free list empty -> evicts cached block a
    assert d == a
    assert alloc.lookup([b"ka"]) == []
    assert alloc.cache_evictions == 1
    alloc.check()


def test_prefix_keys_chain():
    from repro.serve import prefix_keys

    t = np.arange(20, dtype=np.int32)
    keys = prefix_keys(t, 8)
    assert len(keys) == 2                       # only full blocks
    # chain keys commit to the whole history, not just the block's tokens
    t2 = t.copy()
    t2[0] = 99
    keys2 = prefix_keys(t2, 8)
    assert keys[0] != keys2[0] and keys[1] != keys2[1]
    # equal prefixes share keys
    assert prefix_keys(t[:16], 8) == keys
    assert prefix_keys(t, 8)[0] == keys[0]
    assert prefix_keys(np.asarray([], np.int32), 8) == []


def test_allocator_exhaustion_and_errors():
    alloc = BlockAllocator(num_blocks=4, block_size=2)
    got = [alloc.alloc() for _ in range(3)]
    assert sorted(got) == [1, 2, 3]
    with pytest.raises(RuntimeError):
        alloc.alloc()
    with pytest.raises(ValueError):
        alloc.free(NULL_BLOCK)
    with pytest.raises(ValueError):
        alloc.free(99)
    alloc.free(got[1])
    assert alloc.alloc() == got[1]              # lowest-id-first reuse
    with pytest.raises(ValueError):
        BlockAllocator(num_blocks=1, block_size=2)
    with pytest.raises(ValueError):
        BlockAllocator(num_blocks=4, block_size=0)


@settings(max_examples=25)
@given(ops=st.lists(st.integers(min_value=0, max_value=11), min_size=0,
                    max_size=60))
def test_slot_tables_compaction_invariants(ops):
    """Random append/release walks over slots: every table row stays a
    contiguous prefix of live blocks (the compaction invariant), no block
    is mapped by two slots, and release returns exactly what was mapped."""
    alloc = BlockAllocator(num_blocks=10, block_size=4)
    tables = SlotTables(max_slots=3, blocks_per_slot=3)
    for op in ops:
        slot = op % 3
        if op < 9:                             # bias towards appending
            if alloc.num_free and tables.mapped(slot) < tables.blocks_per_slot:
                tables.append(slot, alloc.alloc())
        else:
            before = tables.blocks(slot)
            freed = tables.release(slot)
            assert tuple(freed) == before
            for b in freed:
                alloc.free(b)
        tables.check()
        alloc.check()
        total_mapped = sum(tables.mapped(s) for s in range(3))
        assert total_mapped == alloc.in_use
    tables.check()


def test_slot_tables_errors():
    tables = SlotTables(max_slots=2, blocks_per_slot=2)
    with pytest.raises(ValueError):
        tables.append(0, NULL_BLOCK)
    tables.append(0, 1)
    tables.append(0, 2)
    with pytest.raises(ValueError):
        tables.append(0, 3)                     # row full
    assert tables.release(1) == []


def test_blocks_for():
    assert blocks_for(0, 4) == 0
    assert blocks_for(1, 4) == 1
    assert blocks_for(4, 4) == 1
    assert blocks_for(5, 4) == 2


# ---------------------------------------------------------------------------
# Paged attention: plumbing + kernel vs oracle
# ---------------------------------------------------------------------------


def test_paged_write_gather_roundtrip():
    from repro.models.attention import (
        paged_gather, paged_write_positions, paged_write_token)

    rng = np.random.default_rng(0)
    NB, bs, Hk, D = 9, 4, 2, 8
    pool = jnp.asarray(rng.normal(size=(NB, bs, Hk, D)), jnp.float32)
    tables = jnp.asarray([[1, 2, 0], [4, 5, 6]], jnp.int32)

    # per-lane token write lands at the logical position
    lengths = jnp.asarray([5, 9], jnp.int32)
    new = jnp.asarray(rng.normal(size=(2, Hk, D)), jnp.float32)
    lanes = paged_gather(paged_write_token(pool, tables, lengths, new), tables)
    for b in range(2):
        np.testing.assert_array_equal(
            np.asarray(lanes[b, int(lengths[b])]), np.asarray(new[b]))

    # chunk write: valid positions land in order, invalid go to the sink
    pos = jnp.arange(4) + 2
    vals = jnp.asarray(rng.normal(size=(4, Hk, D)), jnp.float32)
    out = paged_write_positions(pool, tables[0], pos, vals, valid=pos < 5)
    lane = paged_gather(out, tables[0][None])[0]
    np.testing.assert_array_equal(np.asarray(lane[2:5]), np.asarray(vals[:3]))
    # the sink (block 0) rows never appear at mapped positions
    np.testing.assert_array_equal(
        np.asarray(lane[5]), np.asarray(pool[2, 1]))   # untouched


@pytest.mark.parametrize("window,softcap", [(0, 0.0), (4, 0.0), (0, 5.0)])
def test_paged_kernel_matches_ref(window, softcap):
    from repro.kernels.paged_attention.ops import paged_attention
    from repro.kernels.paged_attention.ref import paged_attention_ref

    rng = np.random.default_rng(1)
    B, Hk, rep, D, NB, bs, nb = 3, 2, 3, 16, 9, 4, 6
    q = jnp.asarray(rng.normal(size=(B, Hk, rep, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(NB, bs, Hk, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(NB, bs, Hk, D)), jnp.float32)
    lengths = jnp.asarray([5, 0, 13], jnp.int32)
    tables = jnp.asarray(
        [[1, 2, 0, 0, 0, 0], [3, 0, 0, 0, 0, 0], [4, 5, 6, 7, 0, 0]],
        jnp.int32)
    out = paged_attention(q, kp, vp, lengths, tables,
                          window=window, softcap=softcap, interpret=True)
    ref = paged_attention_ref(q, kp, vp, lengths, tables,
                              window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_paged_decode_attention_matches_slotted(setup):
    """The jnp reference path must equal the slotted ``decode_attention``
    bitwise on equal logical inputs — the anchor of engine-level parity."""
    from repro.models.attention import (
        DecodeSharding, decode_attention, paged_decode_attention,
        paged_write_positions)

    cfg, mesh, rules, params = setup
    rng = np.random.default_rng(2)
    B, Hk, rep, D = 2, 1, 3, 16
    S, bs = 16, 4
    lengths = jnp.asarray([5, 9], jnp.int32)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    kv = rng.normal(size=(2, B, S, Hk, D)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(B, Hk, rep, D)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(B, Hk, D)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(B, Hk, D)), jnp.float32)

    # slotted layout: (B, S, ...) lanes
    k_lane, v_lane = jnp.asarray(kv[0]), jnp.asarray(kv[1])
    dec = DecodeSharding.choose(mesh, B)
    want, _, _ = decode_attention(
        q, k_lane, v_lane, kn, vn, lengths, sharding=dec)

    # paged layout: same logical contents scattered through the tables
    pools = []
    for lane in kv:
        pool = jnp.zeros((9, bs, Hk, D), jnp.float32)
        for b in range(B):
            pool = paged_write_positions(
                pool, tables[b], jnp.arange(S), jnp.asarray(lane[b]))
        pools.append(pool)
    got, _, _ = paged_decode_attention(
        q, pools[0], pools[1], kn, vn, lengths, tables)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Engine-level layout parity
# ---------------------------------------------------------------------------


def _staggered_tokens(cfg, mesh, rules, params, ec):
    rng = np.random.default_rng(3)
    lens = [5, 11, 8, 14, 4]
    budgets = [7, 3, 5, 2, 6]
    prompts = _prompts(cfg, rng, lens)
    eng = ServeEngine(cfg, mesh, rules, params, ec)
    rids = [eng.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)]
    eng.drain()
    return [list(eng.completions[r].tokens) for r in rids], eng


def test_paged_matches_slotted_staggered(setup):
    """THE paged-correctness property: on a staggered trace (more requests
    than lanes, heterogeneous lengths, lanes reused), the paged engine —
    whole-bucket prefill — produces exactly the slotted engine's greedy
    tokens, while reserving strictly less KV HBM."""
    cfg, mesh, rules, params = setup
    want, slotted = _staggered_tokens(
        cfg, mesh, rules, params, EngineConfig(max_slots=2, max_len=32))
    got, paged = _staggered_tokens(
        cfg, mesh, rules, params,
        EngineConfig(max_slots=2, max_len=32, kv_layout="paged",
                     page_size=8, num_blocks=7))
    assert got == want
    assert paged.kv_reserved_bytes < slotted.kv_reserved_bytes
    assert paged.stats["kv_peak_used_bytes"] <= paged.kv_reserved_bytes
    # every block returned to the pool once the trace drained
    assert paged.alloc.in_use == 0
    paged.alloc.check()
    paged.tables.check()


def test_chunked_prefill_matches_slotted(setup):
    """Chunked prefill (prompts admitted 4 tokens per step, interleaved
    with decode) must still match the slotted engine's greedy tokens."""
    cfg, mesh, rules, params = setup
    want, _ = _staggered_tokens(
        cfg, mesh, rules, params, EngineConfig(max_slots=2, max_len=32))
    got, eng = _staggered_tokens(
        cfg, mesh, rules, params,
        EngineConfig(max_slots=2, max_len=32, kv_layout="paged",
                     page_size=8, num_blocks=7, prefill_chunk=4))
    assert got == want
    # chunking really happened: more chunk calls than prompts
    assert eng.counters["prefill_chunks"] > eng.counters["prefills"]


def test_paged_pallas_backend_matches(setup):
    cfg, mesh, rules, params = setup
    want, _ = _staggered_tokens(
        cfg, mesh, rules, params, EngineConfig(max_slots=2, max_len=32))
    got, _ = _staggered_tokens(
        cfg, mesh, rules, params,
        EngineConfig(max_slots=2, max_len=32, kv_layout="paged",
                     page_size=8, paged_attn="pallas"))
    assert got == want


def test_paged_block_budget_gates_admission(setup):
    """With a pool too small to hold two worst-case requests, the second
    waits in the queue until the first frees its blocks — and both still
    complete correctly."""
    cfg, mesh, rules, params = setup
    rng = np.random.default_rng(4)
    prompts = _prompts(cfg, rng, [8, 8])
    eng = ServeEngine(
        cfg, mesh, rules, params,
        EngineConfig(max_slots=2, max_len=16, kv_layout="paged",
                     page_size=4, num_blocks=5))   # 4 usable blocks
    rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.step()
    # only one lane admitted: the other's worst case (4 blocks) can't be
    # covered alongside the first's commitment
    assert sum(s is not None for s in eng.slots) == 1
    assert len(eng.queue) == 1
    eng.drain()
    assert all(len(eng.completions[r].tokens) == 6 for r in rids)
    assert eng.alloc.in_use == 0

    # a single request whose worst case exceeds the whole pool can NEVER
    # be admitted: submit refuses it up front
    tiny = ServeEngine(
        cfg, mesh, rules, params,
        EngineConfig(max_slots=2, max_len=16, kv_layout="paged",
                     page_size=4, num_blocks=4),   # 3 usable blocks
        aot=eng.aot,
    )
    with pytest.raises(ValueError):
        tiny.submit(np.arange(8), max_new_tokens=6)   # needs 4 blocks


@pytest.mark.parametrize("kv_layout", ["slotted", "paged"])
def test_engine_steady_builds_flat(setup, kv_layout):
    """Steady state may not build executables on either layout — once
    warm, decode and admission dispatch purely from the AOT cache, and on
    the paged path chunked prefill must not reintroduce per-length
    compiles."""
    cfg, mesh, rules, params = setup
    rng = np.random.default_rng(5)
    paged = dict(kv_layout="paged", page_size=8, prefill_chunk=4)
    eng = ServeEngine(
        cfg, mesh, rules, params,
        EngineConfig(max_slots=2, max_len=32,
                     **(paged if kv_layout == "paged" else {})))
    eng.run(_prompts(cfg, rng, [3, 9, 14]), max_new_tokens=3)
    builds = eng.stats["builds"]
    # slotted: decode + prefill@16; paged: decode + first-chunk +
    # continuation-chunk executables; nothing else
    assert builds == (3 if kv_layout == "paged" else 2)
    eng.run(_prompts(cfg, rng, [5, 13, 7, 2]), max_new_tokens=4)
    assert eng.stats["builds"] == builds


def test_paged_engine_validation(setup):
    cfg, mesh, rules, params = setup
    with pytest.raises(ValueError):
        ServeEngine(cfg, mesh, rules, params,
                    EngineConfig(kv_layout="bogus"))
    with pytest.raises(ValueError):
        ServeEngine(cfg, mesh, rules, params,
                    EngineConfig(kv_layout="slotted", prefill_chunk=8))
    with pytest.raises(ValueError):   # max_len not a multiple of page_size
        ServeEngine(cfg, mesh, rules, params,
                    EngineConfig(max_len=30, kv_layout="paged", page_size=8))
    with pytest.raises(ValueError):   # prefix caching needs block tables
        ServeEngine(cfg, mesh, rules, params,
                    EngineConfig(kv_layout="slotted", prefix_cache=True))
    with pytest.raises(ValueError):   # so does preemption
        ServeEngine(cfg, mesh, rules, params,
                    EngineConfig(kv_layout="slotted", admission="preempt"))
    with pytest.raises(ValueError):
        ServeEngine(cfg, mesh, rules, params,
                    EngineConfig(kv_layout="paged", admission="bogus"))


# ---------------------------------------------------------------------------
# Prefix caching + preemption (engine level)
# ---------------------------------------------------------------------------


def test_prefix_cache_skips_shared_prefill(setup):
    """Two requests sharing a 16-token system prompt: the second admission
    matches the published block chain, prefills only its suffix, and still
    emits exactly the no-cache engine's greedy tokens."""
    cfg, mesh, rules, params = setup
    rng = np.random.default_rng(10)
    sysp = rng.integers(0, cfg.vocab, 16).astype(np.int32)
    prompts = [np.concatenate([sysp, rng.integers(0, cfg.vocab, n)
                               .astype(np.int32)]) for n in (5, 7)]

    def run(prefix):
        eng = ServeEngine(
            cfg, mesh, rules, params,
            EngineConfig(max_slots=1, max_len=32, kv_layout="paged",
                         page_size=8, prefix_cache=prefix))
        out = eng.run(prompts, max_new_tokens=4)
        return [t.tolist() for t in out], eng

    want, plain = run(prefix=False)
    got, cached = run(prefix=True)
    assert got == want
    # max_slots=1 serializes the two requests, so the second's 16 shared
    # positions come from the cache: exactly 16 fewer tokens prefilled
    assert cached.counters["prefix_hit_tokens"] == 16
    assert cached.counters["prefill_tokens"] \
        == plain.counters["prefill_tokens"] - 16
    assert cached.stats["prefix_hit_rate"] > 0.3
    # drained: every block is free or parked in the prefix cache
    assert cached.alloc.in_use == 0
    assert cached.alloc.num_cached > 0
    cached.check_invariants()


def test_prefix_cache_cow_tail(setup):
    """A prompt that is EXACTLY a published block chain (plen % bs == 0)
    must copy-on-write the tail block — the sampling position is
    recomputed in a private copy, never written into the shared block —
    and match the no-cache stream."""
    cfg, mesh, rules, params = setup
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab, 16).astype(np.int32)  # 2 full blocks

    def run(prefix):
        eng = ServeEngine(
            cfg, mesh, rules, params,
            EngineConfig(max_slots=1, max_len=32, kv_layout="paged",
                         page_size=8, prefix_cache=prefix))
        out = eng.run([prompt, prompt.copy()], max_new_tokens=4)
        return [t.tolist() for t in out], eng

    want, _ = run(prefix=False)
    got, eng = run(prefix=True)
    assert got == want
    assert got[0] == got[1]                     # identical prompts agree
    assert eng.counters["cow_copies"] == 1
    # COW recomputes exactly one position: the 15 before it are hits
    assert eng.counters["prefix_hit_tokens"] == 15
    eng.check_invariants()


def test_preempt_requeue_completes_with_parity(setup):
    """A pool too small for every lane's worst case under
    admission='preempt': lanes are admitted on immediate need, decode
    growth preempts the lowest-priority lane back to the queue, and every
    request still finishes with the slotted engine's exact tokens."""
    cfg, mesh, rules, params = setup
    rng = np.random.default_rng(12)
    prompts = _prompts(cfg, rng, [9, 12, 7])
    budgets = [8, 6, 7]

    def run(ec):
        eng = ServeEngine(cfg, mesh, rules, params, ec)
        rids = [eng.submit(p, max_new_tokens=b)
                for p, b in zip(prompts, budgets)]
        while eng.has_work():
            eng.step()
            eng.check_invariants()
        return [list(eng.completions[r].tokens) for r in rids], eng

    want, _ = run(EngineConfig(max_slots=3, max_len=32))
    got, eng = run(EngineConfig(
        max_slots=3, max_len=32, kv_layout="paged", page_size=8,
        num_blocks=7, admission="preempt"))       # 6 usable blocks for 3 lanes
    assert got == want
    assert eng.counters["preemptions"] > 0
    assert eng.counters["resumed"] == eng.counters["preemptions"]
    assert eng.counters["admitted"] == eng.counters["evicted"] == len(prompts)
    assert eng.alloc.in_use == 0


def test_preempt_stochastic_resume_is_coherent(setup):
    """Preempting a temperature>0 lane must not fork its stream: during
    the resume replay the engine forces the RECORDED tokens as decode
    inputs (a re-sample at a different key-stream position would diverge
    from the emitted history and poison the prefix index).  Completions
    keep exactly their budget, runs are seed-deterministic, and the
    replay machinery demonstrably fired."""
    cfg, mesh, rules, params = setup
    rng = np.random.default_rng(15)
    prompts = _prompts(cfg, rng, [9, 12, 7])
    budgets = [8, 6, 7]

    def run():
        eng = ServeEngine(cfg, mesh, rules, params, EngineConfig(
            max_slots=3, max_len=32, kv_layout="paged", page_size=8,
            num_blocks=7, admission="preempt", seed=11))
        rids = [eng.submit(p, max_new_tokens=b, temperature=1.5)
                for p, b in zip(prompts, budgets)]
        while eng.has_work():
            eng.step()
            eng.check_invariants()
        return [list(eng.completions[r].tokens) for r in rids], eng

    a, eng = run()
    assert eng.counters["preemptions"] > 0
    assert eng.counters["replayed_tokens"] > 0
    for tokens, b in zip(a, budgets):
        assert len(tokens) == b
    b_, _ = run()
    assert a == b_                               # seed-deterministic


def test_preempt_single_lane_never_livelocks(setup):
    """A single request whose worst case fits the pool exactly must run
    to completion alone — preemption never evicts the only lane into an
    infinite requeue loop."""
    cfg, mesh, rules, params = setup
    rng = np.random.default_rng(13)
    prompt = _prompts(cfg, rng, [9])[0]
    eng = ServeEngine(
        cfg, mesh, rules, params,
        EngineConfig(max_slots=2, max_len=16, kv_layout="paged",
                     page_size=4, num_blocks=5, admission="preempt"))
    rid = eng.submit(prompt, max_new_tokens=8)    # needs all 4 usable blocks
    for _ in range(200):
        if not eng.step():
            break
        eng.check_invariants()
    assert len(eng.completions[rid].tokens) == 8


def test_prebuild_covers_prefix_and_preempt_dispatch(setup):
    """After ``prebuild()``, no schedule — prefix hits, misses, COW,
    preemption resumes — may compile another executable (the builds-flat
    guarantee CI leans on)."""
    cfg, mesh, rules, params = setup
    rng = np.random.default_rng(14)
    eng = ServeEngine(
        cfg, mesh, rules, params,
        EngineConfig(max_slots=2, max_len=32, kv_layout="paged",
                     page_size=8, num_blocks=9, prefix_cache=True,
                     admission="preempt"))
    eng.prebuild()
    builds = eng.stats["builds"]
    sysp = rng.integers(0, cfg.vocab, 16).astype(np.int32)
    prompts = [np.concatenate([sysp, t]) for t in _prompts(cfg, rng, [4, 6])]
    prompts += [sysp.copy()] + _prompts(cfg, rng, [11, 3])
    eng.run(prompts, max_new_tokens=6)
    assert eng.counters["prefix_hit_tokens"] > 0
    assert eng.stats["builds"] == builds
