"""Continuous-batching serve engine: admit/evict invariants, per-slot
decode correctness vs the static loop, prefill bucket reuse (flat build
counter), fused-sampler determinism, EOS eviction, and AotCache counters
for both the train (SynkFunction) and serve callers."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_smoke_config
from repro.core.aot import AotCache
from repro.models import registry
from repro.serve import (
    EngineConfig,
    RecurrentCache,
    ServeConfig,
    ServeEngine,
    bucket_for,
    generate,
    generate_static,
    prompt_buckets,
    slot_decode_program,
    slot_prefill_program,
    slot_state_specs,
)
from repro.train.step import shardings_for


@pytest.fixture(scope="module")
def setup():
    from repro.launch.mesh import single_device_mesh
    from repro.models.common import ShardRules

    mesh = single_device_mesh()
    rules = ShardRules.for_mesh(mesh)
    # f32 so greedy comparisons against the static loop are exact
    cfg = dataclasses.replace(
        get_smoke_config("smollm-360m"), compute_dtype="float32")
    params = registry.get_module(cfg).init(cfg, jax.random.PRNGKey(0))
    return cfg, mesh, rules, params


def _family_setup(arch: str):
    from repro.launch.mesh import single_device_mesh
    from repro.models.common import ShardRules

    mesh = single_device_mesh()
    rules = ShardRules.for_mesh(mesh)
    cfg = dataclasses.replace(
        get_smoke_config(arch), compute_dtype="float32")
    params = registry.get_module(cfg).init(cfg, jax.random.PRNGKey(0))
    return cfg, mesh, rules, params


@pytest.fixture(scope="module")
def rec_setup():
    """xLSTM smoke config — the ``ssm`` family, state kind 'recurrent'."""
    return _family_setup("xlstm-1.3b")


@pytest.fixture(scope="module")
def hyb_setup():
    """Zamba2 smoke config — the ``hybrid`` family (KV + recurrent)."""
    return _family_setup("zamba2-1.2b")


def _prompts(cfg, rng, lens):
    return [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lens]


# ---------------------------------------------------------------------------
# Slot lifecycle
# ---------------------------------------------------------------------------


def test_admit_evict_invariants(setup):
    """Scripted schedule: more requests than slots, heterogeneous budgets.
    Slots never oversubscribe, every request gets exactly its budget, and
    the engine counters balance."""
    cfg, mesh, rules, params = setup
    rng = np.random.default_rng(0)
    budgets = [3, 1, 6, 2, 4, 2, 5, 1]
    prompts = _prompts(cfg, rng, [4, 9, 5, 12, 3, 7, 6, 4])
    eng = ServeEngine(cfg, mesh, rules, params,
                      EngineConfig(max_slots=3, max_len=32))
    rids = [eng.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)]

    while eng.has_work():
        assert eng.step()
        occupied = sum(s is not None for s in eng.slots)
        assert occupied <= 3
        assert eng.counters["admitted"] - eng.counters["evicted"] == len(eng.live)
        assert len(eng.live) == occupied
    assert not eng.step()                       # idle engine reports no work

    assert eng.counters["admitted"] == eng.counters["evicted"] == len(budgets)
    assert eng.counters["admitted"] > 3         # slots were reused
    for r, b in zip(rids, budgets):
        c = eng.completions[r]
        assert len(c.tokens) == b
        assert all(0 <= t < cfg.vocab for t in c.tokens)


def test_put_copies_host_mirrors(setup):
    """A pushed host mirror must not alias the device array: the engine
    mutates its mirrors in place while a queued executable may still read
    the pushed copy.  On CPU, a 64-byte-aligned host buffer is taken
    zero-copy unless ``_put`` copies it."""
    cfg, mesh, rules, params = setup
    eng = ServeEngine(cfg, mesh, rules, params,
                      EngineConfig(max_slots=3, max_len=32))
    for dtype in (np.int32, np.float32, np.bool_):
        buf = np.zeros(64 + 64, dtype)
        off = (-buf.ctypes.data % 64) // buf.itemsize
        mirror = buf[off: off + 64]
        dev = eng._put(mirror, dtype)
        mirror[:] = 1
        assert not np.asarray(dev).any(), dtype


def test_decode_temp_bytes_gauge_set_at_build(setup):
    """``engine.decode_temp_bytes`` is the compiled decode executable's
    temporaries, set once when the engine builds it."""
    cfg, mesh, rules, params = setup
    eng = ServeEngine(cfg, mesh, rules, params,
                      EngineConfig(max_slots=2, max_len=32))
    gauge = eng.obs.metrics.gauge("engine.decode_temp_bytes")
    assert gauge.value == 0
    exe = eng._decode_exe()
    assert gauge.value == exe.memory_analysis().temp_size_in_bytes > 0
    eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=3)
    eng.drain()
    assert gauge.value == exe.memory_analysis().temp_size_in_bytes


def test_staggered_matches_solo_static(setup):
    """THE continuous-batching correctness property: a request admitted
    mid-flight into a slot (at its own cache position, prompt padded to a
    bucket, batchmates at other positions) must produce exactly the tokens
    the legacy static loop produces for it alone."""
    cfg, mesh, rules, params = setup
    rng = np.random.default_rng(1)
    lens = [5, 11, 8]
    budgets = [7, 3, 5]
    prompts = _prompts(cfg, rng, lens)

    solo = [
        generate_static(cfg, mesh, rules, params, p[None],
                        serve=ServeConfig(max_new_tokens=b))[0]
        for p, b in zip(prompts, budgets)
    ]

    # 2 slots, 3 requests: the third is admitted when a lane frees, while
    # the surviving lane sits mid-sequence at a different length
    eng = ServeEngine(cfg, mesh, rules, params,
                      EngineConfig(max_slots=2, max_len=32))
    rids = [eng.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)]
    eng.drain()
    for r, want in zip(rids, solo):
        got = np.asarray(eng.completions[r].tokens)
        np.testing.assert_array_equal(got, np.asarray(want))


def test_generate_wrapper_greedy_parity(setup):
    """generate() is a thin wrapper over the engine and must match the
    legacy loop token-for-token under greedy decoding."""
    cfg, mesh, rules, params = setup
    rng = np.random.default_rng(2)
    prompts = np.stack(_prompts(cfg, rng, [10, 10, 10]))
    a = generate(cfg, mesh, rules, params, prompts,
                 serve=ServeConfig(max_new_tokens=6))
    b = generate_static(cfg, mesh, rules, params, prompts,
                        serve=ServeConfig(max_new_tokens=6))
    np.testing.assert_array_equal(a, b)
    assert a.shape == (3, 6)


def test_generate_default_serveconfig_not_shared(setup):
    """The old signature had ``serve: ServeConfig = ServeConfig()`` — a
    mutable shared-instance footgun.  Defaulting must build a fresh config
    per call (None sentinel)."""
    import inspect
    from repro.serve import loop

    for fn in (loop.generate, loop.generate_static):
        default = inspect.signature(fn).parameters["serve"].default
        assert default is None


def test_eos_eviction(setup):
    """A lane hitting EOS frees immediately and its tokens end at EOS."""
    cfg, mesh, rules, params = setup
    rng = np.random.default_rng(3)
    prompt = _prompts(cfg, rng, [6])[0]
    # learn what greedy emits, then re-run with that token as EOS
    probe = ServeEngine(cfg, mesh, rules, params,
                        EngineConfig(max_slots=1, max_len=32))
    toks = probe.run([prompt], max_new_tokens=8)[0]
    eos = int(toks[2])

    eng = ServeEngine(cfg, mesh, rules, params,
                      EngineConfig(max_slots=1, max_len=32, eos_id=eos))
    out = eng.run([prompt], max_new_tokens=8)[0]
    assert out[-1] == eos
    assert len(out) <= len(toks)
    assert eos not in out[:-1]
    assert eng.counters["evicted"] == 1


# ---------------------------------------------------------------------------
# Dispatch-cache behavior
# ---------------------------------------------------------------------------


def test_prefill_bucket_reuse(setup):
    """Build count = one decode + one prefill per distinct *bucket*; more
    requests in the same buckets must not build anything new."""
    cfg, mesh, rules, params = setup
    rng = np.random.default_rng(4)
    eng = ServeEngine(cfg, mesh, rules, params,
                      EngineConfig(max_slots=2, max_len=64))
    assert eng.buckets == prompt_buckets(64) == (16, 32, 64)

    eng.run(_prompts(cfg, rng, [3, 9, 14]), max_new_tokens=2)   # bucket 16
    assert eng.stats["builds"] == 2                 # decode + prefill@16
    eng.run(_prompts(cfg, rng, [20, 17]), max_new_tokens=2)     # bucket 32
    assert eng.stats["builds"] == 3
    hits_before = eng.stats["cache_hits"]
    eng.run(_prompts(cfg, rng, [5, 21, 8, 30]), max_new_tokens=3)
    assert eng.stats["builds"] == 3                 # steady state: no builds
    assert eng.stats["cache_hits"] > hits_before
    assert eng.stats["executables"] == 3


def test_bucket_for():
    assert bucket_for(3, (16, 32)) == 16
    assert bucket_for(16, (16, 32)) == 16
    assert bucket_for(17, (16, 32)) == 32
    with pytest.raises(ValueError):
        bucket_for(33, (16, 32))
    assert prompt_buckets(10) == (10,)
    assert prompt_buckets(100) == (16, 32, 64, 100)


def test_bucket_degenerate_inputs():
    """Degenerate inputs must raise loudly instead of silently producing
    empty/garbage bucket tables."""
    with pytest.raises(ValueError):
        prompt_buckets(0)
    with pytest.raises(ValueError):
        prompt_buckets(-3)
    with pytest.raises(ValueError):
        prompt_buckets(64, min_bucket=0)
    with pytest.raises(ValueError):
        prompt_buckets(64, min_bucket=-16)
    with pytest.raises(ValueError):
        bucket_for(0, (16, 32))            # zero-length prompt
    with pytest.raises(ValueError):
        bucket_for(-1, (16, 32))
    with pytest.raises(ValueError):
        bucket_for(4, ())                  # no buckets configured


def test_aot_cache_counters_train_and_serve(setup):
    """The shared AotCache counts builds/hits for both caller families."""
    # unit
    c = AotCache("t")
    assert c.get(("a",), lambda: 41) == 41
    assert c.get(("a",), lambda: 43) == 41          # cached, build not rerun
    assert c.get(("b",), lambda: 42) == 42
    assert c.stats == {"builds": 2, "cache_hits": 1}
    assert len(c) == 2 and ("a",) in c

    # train caller: SynkFunction routes its executables through AotCache
    import repro.core as synk

    synk.reset()
    f = synk.function(lambda x: jnp.sum(x), [synk.Scatter()],
                      synk.Reduce("sum"))
    x = np.arange(8, dtype=np.float32)
    f(x); f(x)
    assert f.stats["builds"] == 1
    assert f.stats["cache_hits"] == 1
    f(np.arange(16, dtype=np.float32))              # new signature
    assert f.stats["builds"] == 2

    # serve caller: engine counters mirror the same schema
    cfg, mesh, rules, params = setup
    eng = ServeEngine(cfg, mesh, rules, params,
                      EngineConfig(max_slots=1, max_len=32))
    eng.run(_prompts(cfg, np.random.default_rng(5), [4]), max_new_tokens=4)
    assert eng.stats["builds"] == 2
    assert eng.stats["cache_hits"] >= 1
    for key in ("admitted", "evicted", "dead_slot_steps", "builds",
                "cache_hits"):
        assert key in eng.stats


# ---------------------------------------------------------------------------
# Fused sampler
# ---------------------------------------------------------------------------


def test_fused_sampler_deterministic_given_seed(setup):
    cfg, mesh, rules, params = setup
    rng = np.random.default_rng(6)
    prompts = _prompts(cfg, rng, [6, 9])

    def run(seed):
        eng = ServeEngine(cfg, mesh, rules, params,
                          EngineConfig(max_slots=2, max_len=32, seed=seed))
        return eng.run(prompts, max_new_tokens=8, temperature=1.5)

    a, b = run(seed=7), run(seed=7)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    c = run(seed=8)
    assert any(not np.array_equal(x, y) for x, y in zip(a, c))


def test_mixed_greedy_and_sampled_lanes(setup):
    """Greedy lanes must stay greedy (= static loop) even while a
    temperature>0 lane shares the decode executable."""
    cfg, mesh, rules, params = setup
    rng = np.random.default_rng(7)
    g_prompt, s_prompt = _prompts(cfg, rng, [8, 8])
    want = generate_static(cfg, mesh, rules, params, g_prompt[None],
                           serve=ServeConfig(max_new_tokens=5))[0]

    eng = ServeEngine(cfg, mesh, rules, params,
                      EngineConfig(max_slots=2, max_len=32))
    rid_g = eng.submit(g_prompt, max_new_tokens=5, temperature=0.0)
    eng.submit(s_prompt, max_new_tokens=5, temperature=2.0)
    eng.drain()
    np.testing.assert_array_equal(
        np.asarray(eng.completions[rid_g].tokens), np.asarray(want))


def test_sample_tokens_shapes():
    from repro.serve import sample_tokens

    logits = jnp.asarray(np.random.default_rng(0).normal(size=(4, 32)),
                         jnp.float32)
    key = jax.random.PRNGKey(0)
    greedy = sample_tokens(logits, key, jnp.zeros(4))
    np.testing.assert_array_equal(
        np.asarray(greedy), np.argmax(np.asarray(logits), -1))
    hot = sample_tokens(logits, key, jnp.full(4, 2.0), top_k=4)
    top4 = np.argsort(np.asarray(logits), -1)[:, -4:]
    for i in range(4):
        assert int(hot[i]) in top4[i]


def test_sample_tokens_per_slot_vectors():
    """Per-slot top_ks/top_ps vectors: row 0 unmasked, row 1 top-k=1
    (degenerates to greedy), row 2 tiny top-p (degenerates to greedy),
    row 3 top-k=4 — each row masked independently."""
    from repro.serve import sample_tokens

    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
    greedy = np.argmax(np.asarray(logits), -1)
    key = jax.random.PRNGKey(1)
    out = sample_tokens(
        logits, key, jnp.full(4, 3.0),
        top_ks=jnp.asarray([0, 1, 0, 4], jnp.int32),
        top_ps=jnp.asarray([0.0, 0.0, 1e-6, 0.0], jnp.float32),
    )
    assert int(out[1]) == greedy[1]
    assert int(out[2]) == greedy[2]
    top4 = np.argsort(np.asarray(logits), -1)[:, -4:]
    assert int(out[3]) in top4[3]
    # off-vectors (0s) must not perturb the unmasked sampling path
    base = sample_tokens(logits, key, jnp.full(4, 3.0))
    masked_off = sample_tokens(
        logits, key, jnp.full(4, 3.0),
        top_ks=jnp.zeros(4, jnp.int32), top_ps=jnp.zeros(4, jnp.float32))
    np.testing.assert_array_equal(np.asarray(base), np.asarray(masked_off))


def test_per_request_sampling_params(setup):
    """submit(top_k=, top_p=) land in the on-device per-slot vectors: a
    hot-temperature lane with top_k=1 (or a tiny top_p) must reproduce the
    greedy stream, while an unconstrained hot lane in the SAME batch
    diverges — all through the fused sampler, no host syncs added."""
    cfg, mesh, rules, params = setup
    rng = np.random.default_rng(8)
    p1, p2 = _prompts(cfg, rng, [8, 8])
    want = generate_static(cfg, mesh, rules, params, p1[None],
                           serve=ServeConfig(max_new_tokens=6))[0]

    eng = ServeEngine(cfg, mesh, rules, params,
                      EngineConfig(max_slots=2, max_len=32))
    rid_k = eng.submit(p1, max_new_tokens=6, temperature=2.0, top_k=1)
    rid_hot = eng.submit(p2, max_new_tokens=6, temperature=2.0)
    eng.drain()
    np.testing.assert_array_equal(
        np.asarray(eng.completions[rid_k].tokens), np.asarray(want))

    eng2 = ServeEngine(cfg, mesh, rules, params,
                       EngineConfig(max_slots=2, max_len=32))
    rid_p = eng2.submit(p1, max_new_tokens=6, temperature=2.0, top_p=1e-9)
    eng2.drain()
    np.testing.assert_array_equal(
        np.asarray(eng2.completions[rid_p].tokens), np.asarray(want))


def test_submit_validation(setup):
    cfg, mesh, rules, params = setup
    eng = ServeEngine(cfg, mesh, rules, params,
                      EngineConfig(max_slots=1, max_len=16))
    with pytest.raises(ValueError):
        eng.submit(np.arange(20), max_new_tokens=2)     # prompt > max bucket
    with pytest.raises(ValueError):
        eng.submit(np.arange(4), max_new_tokens=14)     # overruns max_len
    with pytest.raises(ValueError):
        eng.submit(np.array([], np.int32))


@pytest.mark.parametrize("fixture", ["setup", "hyb_setup"])
def test_slot_programs_return_sampled_tokens(fixture, request):
    """The slot decode and prefill programs, lowered as the engine lowers
    them, sample on device: each returns ``(state', tok)`` where ``state'``
    has exactly the input state's leaves, shapes and dtypes and ``tok`` is
    int32 — ``(max_slots,)`` from decode, ``(1,)`` from prefill.  No
    logits leave either program."""
    cfg, mesh, rules, _ = request.getfixturevalue(fixture)
    max_slots, rep = 4, NamedSharding(mesh, P())
    p_sds = registry.abstract_params(cfg)
    p_sh = shardings_for(mesh, registry.param_pspecs(cfg, rules))
    s_sds, s_sh = slot_state_specs(cfg, mesh, max_slots, 32)
    i32 = lambda shape=(): jax.ShapeDtypeStruct(shape, jnp.int32)
    f32 = jax.ShapeDtypeStruct((), jnp.float32)
    decode = jax.jit(
        slot_decode_program(cfg, mesh, rules, eos_id=2),
        in_shardings=(p_sh, s_sh), out_shardings=(s_sh, rep),
        donate_argnums=(1,)).lower(p_sds, s_sds)
    prefill = jax.jit(
        slot_prefill_program(cfg, mesh, rules, eos_id=2),
        in_shardings=(p_sh, s_sh) + (rep,) * 7, out_shardings=(s_sh, rep),
        donate_argnums=(1,)).lower(
            p_sds, s_sds, i32((1, 16)), i32(), i32(), i32(), f32, i32(), f32)
    aval = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t)
    for lowered, n in ((decode, max_slots), (prefill, 1)):
        state, tok = lowered.out_info
        assert aval(state) == aval(s_sds)
        assert (tok.shape, tok.dtype) == ((n,), jnp.int32)


# ---------------------------------------------------------------------------
# Recurrent state kinds: ssm (xLSTM) + hybrid (Zamba)
# ---------------------------------------------------------------------------


def _staggered_vs_solo(cfg, mesh, rules, params):
    """Shared body: 3 requests through 2 lanes (the third admitted only
    when a lane frees, its batchmate mid-sequence at a different length)
    must reproduce each request's solo ``generate_static`` stream."""
    rng = np.random.default_rng(1)
    lens = [5, 11, 8]
    budgets = [7, 3, 5]
    prompts = _prompts(cfg, rng, lens)
    solo = [
        generate_static(cfg, mesh, rules, params, p[None],
                        serve=ServeConfig(max_new_tokens=b))[0]
        for p, b in zip(prompts, budgets)
    ]
    eng = ServeEngine(cfg, mesh, rules, params,
                      EngineConfig(max_slots=2, max_len=32))
    rids = [eng.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)]
    eng.drain()
    for r, want in zip(rids, solo):
        np.testing.assert_array_equal(
            np.asarray(eng.completions[r].tokens), np.asarray(want))
    return eng


def test_recurrent_staggered_matches_solo_static(rec_setup):
    """The continuous-batching property for a RECURRENT cache: lanes are
    per-lane (ssm_state, conv_state)/mLSTM-state leaves with no seq axis,
    admission snapshots the state at the real prompt end despite bucket
    padding, and staggered decode matches solo static token-for-token."""
    eng = _staggered_vs_solo(*rec_setup)
    assert eng.kind == "recurrent"
    assert eng.stats["state_kind"] == "recurrent"


def test_hybrid_staggered_matches_solo_static(hyb_setup):
    """Zamba lanes compose BOTH state kinds — a slotted KV segment for the
    shared attention block and recurrent mamba leaves — through one cache
    dict; the engine serves them with the same admission/eviction flow."""
    eng = _staggered_vs_solo(*hyb_setup)
    assert eng.kind == "hybrid"
    # the composed cache really holds both kinds
    assert set(eng.rec.leaf_axes) == {"ssm", "conv"}
    assert "k" in eng.state["cache"] and "v" in eng.state["cache"]
    # steady state: more admissions in the warm bucket build nothing
    builds = eng.stats["builds"]
    cfg = hyb_setup[0]
    eng.run(_prompts(cfg, np.random.default_rng(2), [3, 12, 6]),
            max_new_tokens=3)
    assert eng.stats["builds"] == builds


@pytest.mark.parametrize("fixture", ["rec_setup", "hyb_setup"])
def test_recurrent_cache_admit_evict_zeroing(fixture, request):
    """RecurrentCache lifecycle invariants: admission hard-resets a lane
    (fresh snapshot, nothing of the previous occupant), decode freezes
    inactive lanes at zero (evict-time zeroing fused into the decode
    executable), and a drained engine's recurrent leaves are all-zero."""
    cfg, mesh, rules, params = request.getfixturevalue(fixture)
    rng = np.random.default_rng(2)
    eng = ServeEngine(cfg, mesh, rules, params,
                      EngineConfig(max_slots=2, max_len=32))
    assert eng.rec and set(eng.rec.leaf_axes) == set(
        registry.recurrent_leaf_axes(cfg))

    # all lanes start zero
    for i in range(2):
        assert eng.rec.lane_is_zero(eng.state["cache"], i)

    # run a short request next to a long one: the short lane evicts while
    # the long one keeps decoding — its lane must read exactly zero while
    # the survivor's state is non-zero
    p_long, p_short = _prompts(cfg, rng, [6, 4])
    rid_long = eng.submit(p_long, max_new_tokens=10)
    rid_short = eng.submit(p_short, max_new_tokens=2)
    steps = 0
    while rid_short not in eng.completions:
        assert eng.step()
        eng.check_invariants()
        steps += 1
        assert steps < 50
    assert rid_long in eng.live                 # the long lane still decodes
    short_slot = next(i for i, s in enumerate(eng.slots) if s is None)
    live_slot = 1 - short_slot
    assert eng.rec.lane_is_zero(eng.state["cache"], short_slot)
    assert not eng.rec.lane_is_zero(eng.state["cache"], live_slot)

    # admit-time reset: a new request takes over the freed lane and its
    # stream matches solo static — no state of the previous occupant leaks
    p_new = _prompts(cfg, rng, [7])[0]
    want = generate_static(cfg, mesh, rules, params, p_new[None],
                           serve=ServeConfig(max_new_tokens=4))[0]
    rid_new = eng.submit(p_new, max_new_tokens=4)
    eng.drain()
    np.testing.assert_array_equal(
        np.asarray(eng.completions[rid_new].tokens), np.asarray(want))

    # evict-time zeroing: a drained engine holds all-zero recurrent state
    for i in range(2):
        assert eng.rec.lane_is_zero(eng.state["cache"], i)
    assert eng.counters["evicted"] == 3


def test_recurrent_preempt_resume_parity(rec_setup):
    """Preempt-and-requeue for the ssm family: a lane preempted mid-decode
    resumes by re-prefilling ONLY the prompt (bucketed prefill of a
    recurrent state is deterministic, so the snapshot is bitwise) and
    replaying its emitted tokens through decode — the PR-4 policy — and
    the resumed stream equals the unpreempted one token-for-token."""
    cfg, mesh, rules, params = rec_setup
    rng = np.random.default_rng(3)
    prompts = _prompts(cfg, rng, [6, 9])

    def run(preempt_at):
        eng = ServeEngine(cfg, mesh, rules, params,
                          EngineConfig(max_slots=2, max_len=32))
        rids = [eng.submit(p, max_new_tokens=b)
                for p, b in zip(prompts, [8, 5])]
        steps = 0
        while eng.has_work():
            eng.step()
            eng.check_invariants()
            steps += 1
            if steps == preempt_at and eng.slots[0] is not None:
                eng.preempt(0)
        return [list(eng.completions[r].tokens) for r in rids], eng

    want, _ = run(preempt_at=0)
    got, eng = run(preempt_at=3)
    assert eng.counters["preemptions"] == 1
    assert eng.counters["resumed"] == 1
    assert eng.counters["replayed_tokens"] > 0
    assert got == want
    # the preempted request's completion is one stream (no re-emission of
    # replayed tokens)
    assert len(got[0]) == 8


def test_recurrent_rejects_paged_options(rec_setup):
    """Recurrent state has no seq axis: every paged-only option must fail
    loudly at engine construction."""
    cfg, mesh, rules, params = rec_setup
    with pytest.raises(ValueError, match="no seq axis"):
        ServeEngine(cfg, mesh, rules, params,
                    EngineConfig(max_slots=1, max_len=32, kv_layout="paged"))
    for bad in (dict(prefill_chunk=8), dict(prefix_cache=True),
                dict(admission="preempt")):
        with pytest.raises(ValueError, match="paged"):
            ServeEngine(cfg, mesh, rules, params,
                        EngineConfig(max_slots=1, max_len=32, **bad))


def test_recurrent_generate_wrapper_and_bucket_reuse(rec_setup):
    """generate() routes the ssm family through the engine now (it used to
    fall back to the static loop) and matches it token-for-token; repeat
    admissions in the same bucket build nothing new."""
    cfg, mesh, rules, params = rec_setup
    rng = np.random.default_rng(4)
    prompts = np.stack(_prompts(cfg, rng, [8, 8, 8]))
    a = generate(cfg, mesh, rules, params, prompts,
                 serve=ServeConfig(max_new_tokens=5))
    b = generate_static(cfg, mesh, rules, params, prompts,
                        serve=ServeConfig(max_new_tokens=5))
    np.testing.assert_array_equal(a, b)

    eng = ServeEngine(cfg, mesh, rules, params,
                      EngineConfig(max_slots=2, max_len=64))
    eng.run(_prompts(cfg, rng, [3, 9, 14]), max_new_tokens=2)
    builds = eng.stats["builds"]
    assert builds == 2                          # decode + prefill@16
    eng.run(_prompts(cfg, rng, [5, 12, 7, 2]), max_new_tokens=3)
    assert eng.stats["builds"] == builds        # steady state: no builds
