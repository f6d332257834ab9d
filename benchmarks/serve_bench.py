"""Serve benchmark: continuous batching vs the static-batch loop, fused
vs host sampling, on a Poisson arrival trace.  Writes BENCH_serve.json.

Runs on a forced 8-device host mesh (env var must be set before jax
initializes, so run as a script — ``benchmarks/run.py`` spawns it).

    python benchmarks/serve_bench.py --smoke --json BENCH_serve.json

Workload: requests with heterogeneous prompt lengths and a heavy-tailed
token-budget distribution (most requests short, every 8th long) arriving
on a Poisson clock fast enough to keep the system load-saturated.  This is
the regime continuous batching targets: a static batch runs every lane to
the batch's *max* budget (dead slots decode padding) and a whole batch
head-of-line-blocks behind its straggler, while the slotted engine admits
from the queue the step a lane frees.

Modes:
    static_batch      legacy loop: batches of ``max_slots`` in arrival
                      order, prefill+decode executables built ONCE and
                      reused (a *stronger* baseline than ``generate()``,
                      which re-traces every call), host-side sampling.
    continuous_fused  the serve engine: slotted cache, fused sampling,
                      AOT-cached dispatch.  The headline.
    continuous_host   engine with ``fused_sampling=False``: full logits
                      round-trip + host sampling per step (ablates the
                      fused sampler).
    continuous_paged  paged (block-table) KV layout with the pool sized
                      to HALF the slotted worst case — ``kv_reserved_
                      bytes`` drops accordingly while greedy tokens stay
                      identical (asserted into ``headline.paged_greedy_
                      parity``; ci.sh gates on it).
    continuous_paged_chunked
                      paged + chunked prefill: prompts admitted in fixed
                      chunks interleaved with decode steps.
    continuous_paged_shared
                      paged engine on the SHARED-PREFIX trace (every
                      prompt opens with the same 48-token system prompt)
                      with prefix caching OFF — the comparator for the
                      prefix mode's prefill-token savings.
    continuous_paged_prefix
                      same shared-prefix trace with the refcounted prefix
                      cache ON: admissions match the published block
                      chains and prefill only their unique suffix.
                      ``timed.prefix_hit_rate`` and the prefill-token
                      ratio vs continuous_paged_shared are the headline
                      (ci.sh gates hit rate > 0 and ratio < 0.6).
    continuous_paged_preempt
                      paged engine with ``admission="preempt"`` and the
                      pool squeezed to ~3/8 of worst case: lanes admit on
                      immediate need and decode growth evicts the lowest-
                      priority lane back to the queue (exact greedy
                      parity still required — ``headline.preempt_greedy_
                      parity``).
    continuous_tiered the preempting pool with the host RAM tier ON:
                      every preemption DMAs the victim's KV blocks to
                      pinned host buffers and re-admission restores them
                      O(bytes copied) — ``restores > 0`` with zero
                      ``replayed_tokens`` and zero re-prefill, tokens
                      bitwise the roomy-pool paged drive (ci.sh gates
                      ``tiered_o_copy_resume``, parity, builds-flat).
    continuous_recurrent
                      the SAME engine serving the ``ssm`` family (xLSTM
                      smoke config): lanes are per-lane recurrent state
                      with no seq axis — admission snapshots the state at
                      the prompt end, eviction zeroes the lane.  Greedy
                      parity vs solo ``generate_static`` and a preempt-
                      and-requeue resume parity are asserted into
                      ``headline.recurrent_greedy_parity`` /
                      ``recurrent_preempt_parity`` (ci.sh gates both).
                      f32 compute so the engine-vs-static comparison is
                      exact.
    continuous_hybrid the engine serving zamba2 (``hybrid``): each lane
                      composes a slotted KV segment (shared attention
                      block) with recurrent mamba leaves — one cache
                      dict, same admission/eviction flow
                      (``headline.hybrid_greedy_parity``).
    continuous_router the multi-replica front-end: a ``Router`` over 3
                      slotted replicas (one weight copy, one shared
                      AotCache), all requests submitted up front, one
                      replica KILLED at a fixed tick mid-drive and a
                      second drained + reinstated.  Every request must
                      finish ``ok`` on a survivor with greedy tokens
                      bitwise the fault-free single-engine drive
                      (``failover_parity``), zero requests lost, and no
                      steady-state builds (ci.sh gates all four plus
                      failovers > 0).
    continuous_chaos  the paged engine under a seeded ``FaultPlan``
                      (injected non-finite logits, failed allocs, prefill
                      and sched-push faults) with a generous retry budget:
                      every request must still terminate ``ok`` with
                      greedy tokens identical to the fault-free drive of
                      the same trace, while dispatching purely from the
                      prebuilt cache.  Reports ``recovery_overhead`` (wall
                      vs the fault-free drive) — the cost of quarantine +
                      preempt-and-replay recovery (ci.sh gates faults
                      fired > 0, parity, and builds-flat).
    continuous_spec   speculative decoding: the slotted engine with a
                      draft model (same architecture, params mixed toward
                      a fresh init) proposing ``spec_k`` tokens per lane
                      per round, the target verifying all of them in ONE
                      fused dispatch.  Greedy tokens must be bitwise the
                      sequential engine's on the same trace (the accept
                      rule only ever commits the target's own argmax);
                      the headline is ``tokens_per_decode_dispatch`` —
                      committed tokens per lane-round, exactly 1.0 for
                      the sequential engine, > 1.0 when speculation pays
                      (ci.sh gates parity, acceptance > 0, rejections
                      > 0, tpd > 1.0, builds-flat).
    continuous_traced the tracing-overhead harness: a submit-all drain
                      drive untraced (best of 2) vs with the FULL observer
                      armed (span tracer + flight-recorder sink).  Tokens
                      must stay bitwise identical and ``traced_overhead_
                      ratio`` (decode steps/s, traced / untraced) must
                      stay >= 0.95 (ci.sh-gated); the span timeline lands
                      as BENCH_serve_trace.json (Chrome/Perfetto) +
                      .jsonl next to the report.

Every continuous mode reports ``kv_reserved_bytes`` (cache HBM actually
allocated) and ``kv_peak_used_bytes`` (high-water mark of positions/blocks
holding live KV) — the reserved-vs-used gap is the over-allocation the
paged layout removes.

Each engine mode prebuilds its executables (``engine.prebuild()``) and
then runs the trace twice: a warmup pass (arrivals collapsed to t=0),
then the timed pass.  ``steady_builds_delta`` must be 0 for EVERY mode —
the AOT dispatch cache may not miss in steady state (scripts/ci.sh fails
otherwise).  Prefix hits and preemptions make the executable schedule
timing-dependent, which is exactly why prebuild (not the warmup trace) is
what guarantees coverage.  ``timed`` holds the timed-pass-only counter
deltas (prefill tokens, prefix hits, preemptions, COW copies).

Metrics per mode: useful tokens/s (every request's budgeted tokens /
wall), and p50/p99 per-token latency ((last-token-time - arrival) /
tokens, over requests).
"""
from __future__ import annotations

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
# the forced host devices exist only on the CPU backend: never take a chip
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse     # noqa: E402
import dataclasses  # noqa: E402
import json         # noqa: E402
import sys          # noqa: E402
import time         # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax          # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.cache import enable_compile_cache  # noqa: E402


@dataclasses.dataclass
class _Req:
    rid: int
    arrival: float          # seconds from trace start
    prompt: np.ndarray
    budget: int             # tokens to generate


def make_trace(n_requests: int, vocab: int, *, seed: int = 0,
               rate: float = 60.0, long_every: int = 8,
               long_budget: int = 64) -> list[_Req]:
    """Poisson arrivals; short budgets with a deterministic heavy tail
    (every ``long_every``-th request wants ``long_budget`` tokens)."""
    rng = np.random.default_rng(seed)
    t = 0.0
    out = []
    for i in range(n_requests):
        t += rng.exponential(1.0 / rate)
        plen = int(rng.integers(4, 25))
        budget = long_budget if i % long_every == long_every - 1 \
            else int(rng.integers(2, 6))
        out.append(_Req(i, t, rng.integers(0, vocab, plen).astype(np.int32), budget))
    return out


def make_shared_trace(n_requests: int, vocab: int, *, seed: int = 1,
                      rate: float = 60.0, prefix_len: int = 48,
                      long_every: int = 4, long_budget: int = 16) -> list[_Req]:
    """The prefix-cache workload: every prompt opens with the SAME
    ``prefix_len``-token system prompt followed by a short unique tail —
    the chat-serving shape where prefix caching pays (near-zero-cost
    system prompts)."""
    rng = np.random.default_rng(seed)
    sysp = rng.integers(0, vocab, prefix_len).astype(np.int32)
    t = 0.0
    out = []
    for i in range(n_requests):
        t += rng.exponential(1.0 / rate)
        tail = rng.integers(0, vocab, int(rng.integers(4, 17))).astype(np.int32)
        budget = long_budget if i % long_every == long_every - 1 \
            else int(rng.integers(2, 6))
        out.append(_Req(i, t, np.concatenate([sysp, tail]), budget))
    return out


def _percentiles(lat_ms: list[float]) -> dict:
    a = np.asarray(lat_ms)
    return {"p50_ms_per_token": float(np.percentile(a, 50)),
            "p99_ms_per_token": float(np.percentile(a, 99))}


def _summary(wall: float, tokens: int, lat_ms: list[float], **extra) -> dict:
    return {"tokens_per_s": tokens / wall, "useful_tokens": tokens,
            "wall_s": wall, **_percentiles(lat_ms), **extra}


# ---------------------------------------------------------------------------
# Static-batch baseline
# ---------------------------------------------------------------------------


def run_static(cfg, mesh, rules, params, trace: list[_Req], *,
               batch: int, temperature: float = 0.0) -> dict:
    """Fixed batches in arrival order; every lane decodes to the batch-max
    budget; host sampling.  Executables are built once and reused (already
    generous to the baseline — ``generate()`` re-traces per call)."""
    from repro.configs.base import ShapeConfig
    from repro.serve.step import jit_decode_step, jit_prefill

    s_pad = max(r.prompt.size for r in trace)
    max_new = max(r.budget for r in trace)
    max_len = s_pad + max_new
    prefill_fn, _ = jit_prefill(
        cfg, mesh, rules, ShapeConfig("bench", "prefill", s_pad, batch),
        max_len=max_len)
    decode_fn, _ = jit_decode_step(
        cfg, mesh, rules, ShapeConfig("bench", "decode", max_len, batch),
        donate=True)

    def one_batch(group: list[_Req], budget: int):
        """Returns per-step wall times of each produced token row."""
        prompts = np.zeros((batch, s_pad), np.int32)
        for j, r in enumerate(group):
            prompts[j, : r.prompt.size] = r.prompt
        cache, logits = prefill_fn(params, jnp.asarray(prompts), None)
        times = []
        for t in range(budget):
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # host round-trip
            np.asarray(tok)
            times.append(time.perf_counter())
            logits, cache = decode_fn(params, cache, tok, jnp.int32(s_pad + t))
        return times

    # warmup: compile both executables
    one_batch(trace[:batch], 1)

    lat_ms, tokens = [], 0
    t0 = time.perf_counter()
    for i in range(0, len(trace), batch):
        group = trace[i : i + batch]
        # head-of-line: the batch launches once its last member has arrived
        wait = t0 + group[-1].arrival - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        budget = max(r.budget for r in group)
        times = one_batch(group, budget)
        for r in group:
            done = times[r.budget - 1]
            lat_ms.append((done - (t0 + r.arrival)) / r.budget * 1e3)
            tokens += r.budget
    wall = time.perf_counter() - t0
    return _summary(wall, tokens, lat_ms, batches=len(range(0, len(trace), batch)),
                    steps=sum(max(r.budget for r in trace[i:i + batch])
                              for i in range(0, len(trace), batch)))


# ---------------------------------------------------------------------------
# Continuous engine
# ---------------------------------------------------------------------------


_TIMED_KEYS = ("prefill_tokens", "prefix_hit_tokens", "prefix_lookup_tokens",
               "preemptions", "cow_copies")


def run_continuous(cfg, mesh, rules, params, trace: list[_Req], *,
                   max_slots: int, max_len: int, fused: bool,
                   temperature: float = 0.0, kv_layout: str = "slotted",
                   page_size: int = 16, num_blocks: int | None = None,
                   prefill_chunk: int = 0, prefix_cache: bool = False,
                   admission: str = "deficit", aot=None) -> dict:
    from repro.serve import EngineConfig, ServeEngine

    engine = ServeEngine(
        cfg, mesh, rules, params,
        EngineConfig(max_slots=max_slots, max_len=max_len,
                     fused_sampling=fused, kv_layout=kv_layout,
                     page_size=page_size, num_blocks=num_blocks,
                     prefill_chunk=prefill_chunk, prefix_cache=prefix_cache,
                     admission=admission),
        aot=aot,
    )
    # compile everything up front: prefix hits and preemption resumes make
    # the executable schedule timing-dependent, so a warmup *trace* can't
    # guarantee coverage — prebuild makes builds-flat an invariant
    engine.prebuild()

    def play(timed: bool):
        i = 0
        t0 = time.perf_counter()
        while i < len(trace) or engine.has_work():
            now = time.perf_counter() - t0
            while i < len(trace) and (not timed or trace[i].arrival <= now):
                r = trace[i]
                engine.submit(r.prompt, max_new_tokens=r.budget,
                              temperature=temperature, rid=r.rid + (0 if timed else 10**6))
                i += 1
            if not engine.step() and timed and i < len(trace):
                time.sleep(max(0.0, t0 + trace[i].arrival - time.perf_counter()))
        return t0, time.perf_counter() - t0

    play(timed=False)                       # warmup (also warms the prefix cache)
    builds_warm = engine.stats["builds"]
    warm_counters = {k: engine.counters[k] for k in _TIMED_KEYS}
    t0, wall = play(timed=True)
    builds_delta = engine.stats["builds"] - builds_warm
    timed = {k: engine.counters[k] - warm_counters[k] for k in _TIMED_KEYS}
    timed["prefix_hit_rate"] = (
        timed["prefix_hit_tokens"] / timed["prefix_lookup_tokens"]
        if timed["prefix_lookup_tokens"] else 0.0)

    lat_ms, tokens = [], 0
    for r in trace:
        c = engine.completions[r.rid]
        lat_ms.append((c.token_times[-1] - (t0 + r.arrival)) / len(c.tokens) * 1e3)
        tokens += len(c.tokens)
    return _summary(wall, tokens, lat_ms, steady_builds_delta=builds_delta,
                    kv_reserved_bytes=engine.kv_reserved_bytes,
                    kv_peak_used_bytes=engine.stats["kv_peak_used_bytes"],
                    timed=timed, stats=engine.stats,
                    metrics=engine.obs.metrics.snapshot())


def run_chaos(cfg, mesh, rules, params, trace: list[_Req], *,
              max_slots: int, max_len: int, page_size: int,
              num_blocks: int, aot=None) -> dict:
    """Fault-injected drive of the paged engine vs the identical fault-
    free drive: all requests must recover to ``ok`` with bitwise greedy
    tokens (quarantine + preempt-and-replay), and the recovery overhead
    is the walls' ratio.  ``max_retries`` is generous so injected faults
    exhaust the budget only with astronomically bad luck."""
    from repro.serve import EngineConfig, FaultPlan, ServeEngine

    ec = EngineConfig(max_slots=max_slots, max_len=max_len,
                      kv_layout="paged", page_size=page_size,
                      num_blocks=num_blocks, max_retries=8)

    def drive(faults):
        eng = ServeEngine(cfg, mesh, rules, params, ec, aot=aot,
                          faults=faults)
        eng.prebuild()
        b0 = eng.stats["builds"]
        rids = [eng.submit(r.prompt, max_new_tokens=r.budget)
                for r in trace]
        t0 = time.perf_counter()
        eng.drain()
        return eng, rids, time.perf_counter() - t0, \
            eng.stats["builds"] - b0

    clean_eng, rids, clean_wall, _ = drive(None)
    plan = FaultPlan(0, {"decode_logits": 0.02, "prefill": 0.05,
                         "alloc": 0.02, "sched_push": 0.05})
    eng, rids2, wall, builds_delta = drive(plan)

    want = [list(clean_eng.completions[r].tokens) for r in rids]
    got = [list(eng.completions[r].tokens) for r in rids2]
    statuses = [eng.completions[r].status for r in rids2]
    tokens = sum(len(t) for t in got)
    return {
        "tokens_per_s": tokens / wall, "useful_tokens": tokens,
        "wall_s": wall, "clean_wall_s": clean_wall,
        "recovery_overhead": wall / clean_wall,
        "faults_fired": plan.total_fired,
        "fault_sites": plan.stats(),
        "faults_detected": eng.counters["faults_detected"],
        "retries": eng.counters["retries"],
        "preemptions": eng.counters["preemptions"],
        "all_ok": all(s == "ok" for s in statuses),
        "token_parity": got == want,
        "steady_builds_delta": builds_delta,
        "metrics": eng.obs.metrics.snapshot(),
    }


def run_tiered(cfg, mesh, rules, params, trace: list[_Req], *,
               max_slots: int, max_len: int, page_size: int,
               num_blocks: int, preempt_blocks: int, aot=None) -> dict:
    """The host-tier drive: paged engine with ``admission="preempt"`` on
    the same squeezed pool as ``continuous_paged_preempt``, plus a host
    RAM tier — every preemption DMAs the victim's KV blocks to host
    buffers and re-admission restores them O(bytes copied) instead of
    replaying the stream.

    The O(copy) claim is asserted structurally, not by timing: with the
    tier on, preemptions must be > 0 (the pool forces them) while
    ``replayed_tokens`` stays 0 (no restored lane ever re-decoded a
    recorded token) and ``prefill_tokens`` equals the trace's prompt
    tokens exactly (no re-prefill on resume).  Tokens must remain
    bitwise the roomy-pool paged drive's — spill/restore is invisible in
    the output."""
    from repro.serve import EngineConfig, ServeEngine

    def drive(ec):
        eng = ServeEngine(cfg, mesh, rules, params, ec, aot=aot)
        eng.prebuild()
        b0 = eng.stats["builds"]
        rids = [eng.submit(r.prompt, max_new_tokens=r.budget)
                for r in trace]
        t0 = time.perf_counter()
        eng.drain()
        return (eng, [list(eng.completions[r].tokens) for r in rids],
                time.perf_counter() - t0, eng.stats["builds"] - b0)

    # parity target: the roomy half pool never preempts, so its streams
    # are the uninterrupted reference (dispatches purely from cache)
    _, want, _, _ = drive(EngineConfig(
        max_slots=max_slots, max_len=max_len, kv_layout="paged",
        page_size=page_size, num_blocks=num_blocks))
    eng, got, wall, builds_delta = drive(EngineConfig(
        max_slots=max_slots, max_len=max_len, kv_layout="paged",
        page_size=page_size, num_blocks=preempt_blocks,
        admission="preempt", host_tier=True))

    c = eng.counters
    prompt_tokens = sum(int(r.prompt.size) for r in trace)
    tokens = sum(len(t) for t in got)
    return {
        "tokens_per_s": tokens / wall, "useful_tokens": tokens,
        "wall_s": wall,
        "token_parity": got == want,
        "all_ok": all(eng.completions[r].status == "ok"
                      for r in eng.completions),
        "preemptions": c["preemptions"],
        "spills": c["spills"], "restores": c["restores"],
        "spill_drops": c["spill_drops"],
        "spilled_bytes": c["spilled_bytes"],
        "restored_bytes": c["restored_bytes"],
        "replayed_tokens": c["replayed_tokens"],
        "prefill_tokens": c["prefill_tokens"],
        "prompt_tokens": prompt_tokens,
        # every resume was a copy: no replay decode steps, no re-prefill
        "o_copy_resume": bool(
            c["restores"] > 0 and c["replayed_tokens"] == 0
            and c["prefill_tokens"] == prompt_tokens),
        "steady_builds_delta": builds_delta,
        "host_tier": eng.stats["host_tier"],
        "kv_reserved_bytes": eng.kv_reserved_bytes,
        "kv_peak_used_bytes": eng.stats["kv_peak_used_bytes"],
        "metrics": eng.obs.metrics.snapshot(),
    }


def run_router(cfg, mesh, rules, params, trace: list[_Req], *,
               replicas: int, max_slots: int, max_len: int,
               kill_tick: int = 2, drain_tick: int = 5,
               reinstate_tick: int = 8, aot=None) -> dict:
    """Router fleet chaos drive: ``replicas`` slotted engines behind the
    front-end, one killed deterministically at ``kill_tick`` (its
    in-flight requests rebuild from the router's stream mirrors — the
    engine is never touched again), another drained at ``drain_tick``
    and reinstated at ``reinstate_tick``.  Failover parity means every
    recovered stream is bitwise the fault-free single-engine drive."""
    from repro.serve import EngineConfig, Router, RouterConfig, ServeEngine

    ec = EngineConfig(max_slots=max_slots, max_len=max_len)

    ref = ServeEngine(cfg, mesh, rules, params, ec, aot=aot)
    rids = [ref.submit(r.prompt, max_new_tokens=r.budget, rid=r.rid)
            for r in trace]
    ref.drain()
    want = [list(ref.completions[r].tokens) for r in rids]

    router = Router(
        cfg, mesh, rules, params, ec,
        RouterConfig(replicas=replicas, shed_queue_depth=len(trace) + 1),
        aot=aot)
    router.prebuild()
    b0 = router.stats["builds"]
    for r in trace:
        router.submit(r.prompt, max_new_tokens=r.budget, rid=r.rid)
    migrated = 0
    t0 = time.perf_counter()
    guard = 0
    while router.has_work():
        router.step()
        router.check_invariants()
        if router.tick == kill_tick:
            router.kill(replicas - 1)
        if router.tick == drain_tick and \
                router.replicas[0].state == "live" and \
                sum(h.state == "live" for h in router.replicas) >= 2:
            migrated = router.drain(0)
        if router.tick == reinstate_tick and \
                router.replicas[0].state == "drained":
            router.reinstate(0)
        guard += 1
        assert guard < 100_000, "router drive failed to drain"
    wall = time.perf_counter() - t0

    got = [list(router.completions[r.rid].tokens) for r in trace]
    statuses = [router.completions[r.rid].status for r in trace]
    tokens = sum(len(t) for t in got)
    c = router.counters
    return {
        "tokens_per_s": tokens / wall, "useful_tokens": tokens,
        "wall_s": wall, "replicas": replicas,
        "requests_lost": c["submitted"] - len(router.completions),
        "all_ok": all(s == "ok" for s in statuses),
        "failover_parity": got == want,
        "failovers": c["failovers"],
        "migrated": migrated,
        "replicas_dead": c["replicas_dead"],
        "cache_routed": c["cache_routed"],
        "steady_builds_delta": router.stats["builds"] - b0,
        "metrics": router.obs.metrics.snapshot(),
    }


def run_traced(cfg, mesh, rules, params, trace: list[_Req], *,
               max_slots: int, max_len: int, aot=None,
               trace_json: str | None = None,
               trace_jsonl: str | None = None) -> dict:
    """Tracing-overhead harness: the same submit-all drain drive on the
    slotted engine, untraced (best of 2 fresh drives) vs with the FULL
    observer armed (tracer + flight-recorder sink).  Greedy tokens must
    be bitwise identical, decode-step counts equal, builds flat, the
    event stream must validate (spans balanced, every request's timeline
    terminal-complete), and the decode steps/s ratio is the headline —
    ci.sh gates it >= 0.95 (tracing must stay a host-side ring append,
    never a sync)."""
    from repro.obs import Observer, to_chrome_trace, to_jsonl, validate
    from repro.serve import EngineConfig, ServeEngine

    ec = EngineConfig(max_slots=max_slots, max_len=max_len)

    def drive(obs):
        eng = ServeEngine(cfg, mesh, rules, params, ec, aot=aot, obs=obs)
        eng.prebuild()
        b0 = eng.stats["builds"]
        rids = [eng.submit(r.prompt, max_new_tokens=r.budget)
                for r in trace]
        t0 = time.perf_counter()
        eng.drain()
        wall = time.perf_counter() - t0
        toks = [list(eng.completions[r].tokens) for r in rids]
        return (eng, toks, wall, eng.stats["builds"] - b0,
                eng.counters["decode_steps"])

    # untraced baseline: best of 2 fresh drives (first absorbs allocator
    # and page-cache noise; the drive itself is deterministic)
    base_walls, base_builds = [], 0
    for _ in range(2):
        _, base_toks, w, bd, base_steps = drive(None)
        base_walls.append(w)
        base_builds = max(base_builds, bd)
    obs = Observer.full(name="engine")
    eng, toks, wall, builds_delta, steps = drive(obs)
    info = validate(obs.tracer.events)
    if trace_json:
        to_chrome_trace(obs.tracer.events, trace_json)
    if trace_jsonl:
        to_jsonl(obs.tracer.events, trace_jsonl)

    tokens = sum(len(t) for t in toks)
    base_wall = min(base_walls)
    return {
        "tokens_per_s": tokens / wall, "useful_tokens": tokens,
        "wall_s": wall, "untraced_wall_s": base_wall,
        "decode_steps": int(steps),
        "decode_steps_match": int(steps) == int(base_steps),
        # equal step counts, so steps/s ratio reduces to the wall ratio
        "traced_overhead_ratio": base_wall / wall,
        "token_parity": toks == base_toks,
        "trace_events": info["events"], "trace_spans": info["spans"],
        "trace_requests": info["requests"],
        "steady_builds_delta": max(base_builds, builds_delta),
        "metrics": eng.obs.metrics.snapshot(),
    }


def run_spec(cfg, mesh, rules, params, draft_params, trace: list[_Req], *,
             max_slots: int, max_len: int, spec_k: int, aot=None) -> dict:
    """Speculative-decoding drive vs the identical sequential engine on
    the same submit-all trace.  Parity is structural (greedy verify
    commits only the target's own argmax, so drafts gate chain LENGTH,
    never token identity) and asserted bitwise here; the speedup claim
    is ``tokens_per_decode_dispatch`` = committed tokens / lane-rounds —
    the sequential engine is exactly 1.0 per lane-round, so anything
    above 1.0 means each verify dispatch amortizes over >1 committed
    token."""
    from repro.serve import EngineConfig, ServeEngine

    def drive(ec, dp):
        eng = ServeEngine(cfg, mesh, rules, params, ec, aot=aot,
                          draft_params=dp)
        eng.prebuild()
        b0 = eng.stats["builds"]
        rids = [eng.submit(r.prompt, max_new_tokens=r.budget)
                for r in trace]
        t0 = time.perf_counter()
        eng.drain()
        return (eng, [list(eng.completions[r].tokens) for r in rids],
                time.perf_counter() - t0, eng.stats["builds"] - b0)

    base = EngineConfig(max_slots=max_slots, max_len=max_len)
    _, want, seq_wall, _ = drive(base, None)
    eng, got, wall, builds_delta = drive(
        dataclasses.replace(base, spec_draft=cfg, spec_k=spec_k),
        draft_params)

    c = eng.counters
    st = eng.stats
    tokens = sum(len(t) for t in got)
    return {
        "tokens_per_s": tokens / wall, "useful_tokens": tokens,
        "wall_s": wall, "sequential_wall_s": seq_wall,
        "spec_k": spec_k,
        "token_parity": got == want,
        "spec_rounds": c["spec_rounds"],
        "spec_drafted": c["spec_drafted"],
        "spec_accepted": c["spec_accepted"],
        "spec_rejected": c["spec_rejected"],
        "spec_committed": c["spec_committed"],
        "acceptance_rate": st["spec_acceptance_rate"],
        "tokens_per_decode_dispatch": st["tokens_per_decode_dispatch"],
        "steady_builds_delta": builds_delta,
        "metrics": eng.obs.metrics.snapshot(),
    }


def check_recurrent_parity(cfg, trace: list[_Req], *, max_slots: int,
                           max_len: int, preempt_tick: int = 3) -> dict:
    """Greedy parity of the recurrent/hybrid slot engine vs the legacy
    ``generate_static`` loop (each request solo), staggered through fewer
    lanes than requests — plus a preempt-and-requeue drive whose resumed
    streams must still match.  Runs on a single-device mesh (the tested
    exact-parity configuration; the throughput modes use the full local
    mesh)."""
    from repro.launch.mesh import single_device_mesh
    from repro.models import registry
    from repro.models.common import ShardRules
    from repro.serve import EngineConfig, ServeConfig, ServeEngine, \
        generate_static

    mesh = single_device_mesh()
    rules = ShardRules.for_mesh(mesh)
    params = registry.get_module(cfg).init(cfg, jax.random.PRNGKey(0))
    reqs = trace[: 2 * max_slots + 1]           # lanes get reused
    solo = [
        list(generate_static(cfg, mesh, rules, params, r.prompt[None],
                             serve=ServeConfig(max_new_tokens=r.budget))[0])
        for r in reqs
    ]

    def drive(preempts: bool):
        eng = ServeEngine(cfg, mesh, rules, params,
                          EngineConfig(max_slots=max_slots, max_len=max_len))
        rids = [eng.submit(r.prompt, max_new_tokens=r.budget) for r in reqs]
        steps = 0
        # a bounded preemption schedule (not periodic: a replay that spans
        # the period would requeue forever and never make progress)
        schedule = {preempt_tick, 3 * preempt_tick + 2} if preempts else set()
        while eng.has_work():
            eng.step()
            steps += 1
            assert steps < 5000, "parity drive failed to drain"
            if steps in schedule:
                victim = next((i for i, s in enumerate(eng.slots)
                               if s is not None), None)
                if victim is not None:
                    eng.preempt(victim)
        return [list(eng.completions[r].tokens) for r in rids], eng

    plain, _ = drive(preempts=False)
    resumed, peng = drive(preempts=True)
    want = [[int(t) for t in row] for row in solo]
    return {
        "greedy_parity": plain == want,
        "preempt_parity": resumed == want,
        "parity_check_preemptions": peng.counters["preemptions"],
        "replayed_tokens": peng.counters["replayed_tokens"],
    }


def check_paged_parity(cfg, mesh, rules, params, trace: list[_Req], *,
                       max_slots: int, max_len: int, page_size: int,
                       num_blocks: int, preempt_blocks: int,
                       prefill_chunk: int, aot=None) -> dict:
    """Greedy token-for-token parity of every paged engine mode — whole-
    bucket, chunked, prefix-cached, and preempting (squeezed pool) —
    against the slotted engine on a staggered submit-all trace.  Sharing
    the bench modes' AotCache means this compiles nothing new."""
    from repro.serve import EngineConfig, ServeEngine

    reqs = trace[: 2 * max_slots + 1]          # lanes get reused
    prompts = [r.prompt for r in reqs]
    budgets = [r.budget for r in reqs]

    def tokens(ec):
        eng = ServeEngine(cfg, mesh, rules, params, ec, aot=aot)
        rids = [eng.submit(p, max_new_tokens=b)
                for p, b in zip(prompts, budgets)]
        eng.drain()
        return [list(eng.completions[r].tokens) for r in rids], eng

    want, _ = tokens(EngineConfig(max_slots=max_slots, max_len=max_len))
    paged, _ = tokens(EngineConfig(
        max_slots=max_slots, max_len=max_len, kv_layout="paged",
        page_size=page_size, num_blocks=num_blocks))
    chunked, _ = tokens(EngineConfig(
        max_slots=max_slots, max_len=max_len, kv_layout="paged",
        page_size=page_size, num_blocks=num_blocks,
        prefill_chunk=prefill_chunk))
    prefix, _ = tokens(EngineConfig(
        max_slots=max_slots, max_len=max_len, kv_layout="paged",
        page_size=page_size, num_blocks=num_blocks, prefix_cache=True))
    preempt, peng = tokens(EngineConfig(
        max_slots=max_slots, max_len=max_len, kv_layout="paged",
        page_size=page_size, num_blocks=preempt_blocks,
        admission="preempt"))
    return {
        "paged_greedy_parity": paged == want and chunked == want,
        "prefix_greedy_parity": prefix == want,
        "preempt_greedy_parity": preempt == want,
        "parity_check_preemptions": peng.counters["preemptions"],
    }


# ---------------------------------------------------------------------------


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="CI sizes")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    from repro.configs import get_smoke_config
    from repro.launch.mesh import local_mesh
    from repro.models import registry
    from repro.models.common import ShardRules

    # smoke model with the REAL vocab: serving moves (slots, V) logits per
    # step, so a toy vocab would hide exactly the cost the fused sampler
    # removes (the host logits round-trip)
    cfg = dataclasses.replace(
        get_smoke_config("smollm-360m"), vocab=49_152)
    mesh = local_mesh()
    rules = ShardRules.for_mesh(mesh)
    params = registry.get_module(cfg).init(cfg, jax.random.PRNGKey(0))

    n_requests = args.requests or (24 if args.smoke else 64)
    max_slots, long_budget = 8, 64
    trace = make_trace(n_requests, cfg.vocab, long_budget=long_budget)
    shared_trace = make_shared_trace(n_requests, cfg.vocab)
    page_size = 16
    max_len = max(max(r.prompt.size + r.budget for r in trace),
                  max(r.prompt.size + r.budget for r in shared_trace))
    max_len = -(-max_len // page_size) * page_size     # paged wants a multiple
    # paged pool: HALF the slotted worst-case reservation — the layout's
    # point is that the mixed-length trace never needs the worst case —
    # rounded up to the device count (the engine shards the block dim)
    worst_blocks = max_slots * (max_len // page_size)
    ndev = jax.device_count()
    num_blocks = -(-(worst_blocks // 2 + 1) // ndev) * ndev
    # preempting pool: squeezed to just above the largest single request's
    # worst case (the admission floor), so concurrent lanes constantly
    # overcommit it — admission stops gating on worst-case commitments and
    # decode growth preempts instead of waiting
    max_wc = max(-(-(r.prompt.size + r.budget - 1) // page_size)
                 for r in trace)
    preempt_blocks = -(-(max_wc + 2) // ndev) * ndev
    prefill_chunk = 2 * page_size

    report = {
        "meta": {
            "bench": "serve",
            "devices": jax.device_count(),
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "smoke": bool(args.smoke),
            "config": cfg.name,
            "trace": {
                "n_requests": n_requests, "max_slots": max_slots,
                "max_len": max_len, "long_budget": long_budget,
                "useful_tokens": sum(r.budget for r in trace),
                "page_size": page_size, "num_blocks": num_blocks,
                "preempt_blocks": preempt_blocks,
                "prefill_chunk": prefill_chunk,
                "shared_prefix_len": 48,
            },
        },
        "modes": {},
    }
    # one AotCache across every engine: each mode compiles only its own
    # executables (keys carry layout/fused/chunk), and the parity check at
    # the end dispatches entirely from cache
    from repro.core.aot import AotCache
    aot = AotCache("serve-bench")
    report["modes"]["static_batch"] = run_static(
        cfg, mesh, rules, params, trace, batch=max_slots)
    report["modes"]["continuous_fused"] = run_continuous(
        cfg, mesh, rules, params, trace, max_slots=max_slots,
        max_len=max_len, fused=True, aot=aot)
    report["modes"]["continuous_host"] = run_continuous(
        cfg, mesh, rules, params, trace, max_slots=max_slots,
        max_len=max_len, fused=False, aot=aot)
    report["modes"]["continuous_paged"] = run_continuous(
        cfg, mesh, rules, params, trace, max_slots=max_slots,
        max_len=max_len, fused=True, kv_layout="paged",
        page_size=page_size, num_blocks=num_blocks, aot=aot)
    report["modes"]["continuous_paged_chunked"] = run_continuous(
        cfg, mesh, rules, params, trace, max_slots=max_slots,
        max_len=max_len, fused=True, kv_layout="paged",
        page_size=page_size, num_blocks=num_blocks,
        prefill_chunk=prefill_chunk, aot=aot)
    # the shared-prefix pair: identical trace, prefix cache off vs on —
    # the prefill-token delta is the work the cache removes
    report["modes"]["continuous_paged_shared"] = run_continuous(
        cfg, mesh, rules, params, shared_trace, max_slots=max_slots,
        max_len=max_len, fused=True, kv_layout="paged",
        page_size=page_size, num_blocks=num_blocks, aot=aot)
    report["modes"]["continuous_paged_prefix"] = run_continuous(
        cfg, mesh, rules, params, shared_trace, max_slots=max_slots,
        max_len=max_len, fused=True, kv_layout="paged",
        page_size=page_size, num_blocks=num_blocks, prefix_cache=True,
        aot=aot)
    report["modes"]["continuous_paged_preempt"] = run_continuous(
        cfg, mesh, rules, params, trace, max_slots=max_slots,
        max_len=max_len, fused=True, kv_layout="paged",
        page_size=page_size, num_blocks=preempt_blocks,
        admission="preempt", aot=aot)
    report["modes"]["continuous_tiered"] = run_tiered(
        cfg, mesh, rules, params, trace, max_slots=max_slots,
        max_len=max_len, page_size=page_size, num_blocks=num_blocks,
        preempt_blocks=preempt_blocks, aot=aot)
    report["modes"]["continuous_chaos"] = run_chaos(
        cfg, mesh, rules, params, trace, max_slots=max_slots,
        max_len=max_len, page_size=page_size, num_blocks=num_blocks,
        aot=aot)
    report["modes"]["continuous_router"] = run_router(
        cfg, mesh, rules, params, trace, replicas=3, max_slots=max_slots,
        max_len=max_len, aot=aot)
    # tracing overhead + trace artifacts next to the report json
    trace_json = trace_jsonl = None
    if args.json:
        base = args.json[:-5] if args.json.endswith(".json") else args.json
        trace_json, trace_jsonl = base + "_trace.json", base + "_trace.jsonl"
    report["modes"]["continuous_traced"] = run_traced(
        cfg, mesh, rules, params, trace, max_slots=max_slots,
        max_len=max_len, aot=aot, trace_json=trace_json,
        trace_jsonl=trace_jsonl)
    # speculative decoding: draft = same arch mixed 10% toward a fresh
    # init — close enough to accept routinely, far enough to reject
    # routinely, so both the commit and rollback paths are timed
    draft_params = jax.tree.map(
        lambda a, b: 0.9 * a + 0.1 * b, params,
        registry.get_module(cfg).init(cfg, jax.random.PRNGKey(1)))
    report["modes"]["continuous_spec"] = run_spec(
        cfg, mesh, rules, params, draft_params, trace,
        max_slots=max_slots, max_len=max_len, spec_k=3, aot=aot)

    # --- recurrent state kinds: the SAME engine over ssm + hybrid ------
    # f32 compute so the engine-vs-generate_static parity checks are
    # exact; the smoke vocabs stay native (these modes measure the family
    # axis + dispatch flatness, not sampler-fetch bandwidth)
    rec_parity = {}
    for mode_name, arch in (("continuous_recurrent", "xlstm-1.3b"),
                            ("continuous_hybrid", "zamba2-1.2b")):
        fcfg = dataclasses.replace(
            get_smoke_config(arch), compute_dtype="float32")
        fparams = registry.get_module(fcfg).init(fcfg, jax.random.PRNGKey(0))
        ftrace = make_trace(max(n_requests // 2, 8), fcfg.vocab,
                            long_budget=32)
        fmax_len = max(r.prompt.size + r.budget for r in ftrace) + 8
        faot = AotCache(mode_name)
        report["modes"][mode_name] = run_continuous(
            fcfg, mesh, ShardRules.for_mesh(mesh), fparams, ftrace,
            max_slots=max_slots, max_len=fmax_len, fused=True, aot=faot)
        rec_parity[mode_name] = check_recurrent_parity(
            fcfg, ftrace, max_slots=max(max_slots // 4, 2),
            max_len=fmax_len)

    st, cf = report["modes"]["static_batch"], report["modes"]["continuous_fused"]
    pg = report["modes"]["continuous_paged"]
    px = report["modes"]["continuous_paged_prefix"]
    shared = report["modes"]["continuous_paged_shared"]
    parity = check_paged_parity(
        cfg, mesh, rules, params, trace, max_slots=max_slots,
        max_len=max_len, page_size=page_size, num_blocks=num_blocks,
        preempt_blocks=preempt_blocks, prefill_chunk=prefill_chunk, aot=aot)
    report["headline"] = {
        "speedup_vs_static": cf["tokens_per_s"] / st["tokens_per_s"],
        "p99_ratio_vs_static": cf["p99_ms_per_token"] / st["p99_ms_per_token"],
        "fused_speedup_vs_host": (
            cf["tokens_per_s"]
            / report["modes"]["continuous_host"]["tokens_per_s"]),
        "steady_builds_delta": cf["steady_builds_delta"],
        "paged_steady_builds_delta": max(
            pg["steady_builds_delta"],
            report["modes"]["continuous_paged_chunked"]["steady_builds_delta"]),
        # ALL engine modes must dispatch purely from cache after warmup
        "all_steady_builds_delta": max(
            row["steady_builds_delta"]
            for name, row in report["modes"].items()
            if name != "static_batch"),
        "kv_reserved_ratio_paged_vs_slotted": (
            pg["kv_reserved_bytes"] / cf["kv_reserved_bytes"]),
        # prefix caching: timed-pass hit rate and the fraction of prefill
        # tokens still computed vs the no-cache engine on the same trace
        "prefix_cache_hit_rate": px["timed"]["prefix_hit_rate"],
        "prefix_prefill_token_ratio": (
            px["timed"]["prefill_tokens"]
            / max(shared["timed"]["prefill_tokens"], 1)),
        "preemptions_timed": (
            report["modes"]["continuous_paged_preempt"]["timed"]["preemptions"]),
        # host tier: every preemption resumed O(copy) — restores > 0 with
        # zero replayed decode steps and zero re-prefill — bitwise the
        # roomy-pool paged streams, dispatching purely from cache
        "tiered_token_parity": (
            report["modes"]["continuous_tiered"]["token_parity"]),
        "tiered_restores": report["modes"]["continuous_tiered"]["restores"],
        "tiered_replayed_tokens": (
            report["modes"]["continuous_tiered"]["replayed_tokens"]),
        "tiered_o_copy_resume": (
            report["modes"]["continuous_tiered"]["o_copy_resume"]),
        "tiered_steady_builds_delta": (
            report["modes"]["continuous_tiered"]["steady_builds_delta"]),
        # chaos: injected faults must all recover — same greedy tokens as
        # the fault-free drive, no retraces, bounded overhead
        "chaos_faults_fired": (
            report["modes"]["continuous_chaos"]["faults_fired"]),
        "chaos_all_ok": report["modes"]["continuous_chaos"]["all_ok"],
        "chaos_token_parity": (
            report["modes"]["continuous_chaos"]["token_parity"]),
        "chaos_recovery_overhead": (
            report["modes"]["continuous_chaos"]["recovery_overhead"]),
        "chaos_steady_builds_delta": (
            report["modes"]["continuous_chaos"]["steady_builds_delta"]),
        # router fleet: a replica crash mid-drive must be invisible in
        # the output — zero lost, all ok, bitwise the single-engine run
        "router_requests_lost": (
            report["modes"]["continuous_router"]["requests_lost"]),
        "router_all_ok": report["modes"]["continuous_router"]["all_ok"],
        "router_failover_parity": (
            report["modes"]["continuous_router"]["failover_parity"]),
        "router_failovers": (
            report["modes"]["continuous_router"]["failovers"]),
        "router_migrated": report["modes"]["continuous_router"]["migrated"],
        "router_steady_builds_delta": (
            report["modes"]["continuous_router"]["steady_builds_delta"]),
        # recurrent/hybrid: slot serving generalized beyond the lm
        # families — engine-vs-static greedy parity, preempt-resume
        # parity (ssm), and dispatch flatness across both new modes
        "recurrent_greedy_parity":
            rec_parity["continuous_recurrent"]["greedy_parity"],
        "recurrent_preempt_parity":
            rec_parity["continuous_recurrent"]["preempt_parity"],
        "recurrent_preemptions":
            rec_parity["continuous_recurrent"]["parity_check_preemptions"],
        "hybrid_greedy_parity":
            rec_parity["continuous_hybrid"]["greedy_parity"],
        "hybrid_preempt_parity":
            rec_parity["continuous_hybrid"]["preempt_parity"],
        "recurrent_steady_builds_delta": max(
            report["modes"]["continuous_recurrent"]["steady_builds_delta"],
            report["modes"]["continuous_hybrid"]["steady_builds_delta"]),
        # speculative decoding: bitwise greedy parity with the
        # sequential engine while each verify dispatch commits > 1
        # token per lane-round on average
        "spec_greedy_parity": (
            report["modes"]["continuous_spec"]["token_parity"]),
        "spec_acceptance_rate": (
            report["modes"]["continuous_spec"]["acceptance_rate"]),
        "spec_tokens_per_decode_dispatch": (
            report["modes"]["continuous_spec"]
            ["tokens_per_decode_dispatch"]),
        "spec_steady_builds_delta": (
            report["modes"]["continuous_spec"]["steady_builds_delta"]),
        # observability: a fully-armed observer (tracer + flight
        # recorder) must not perturb the engine — bitwise tokens, no new
        # builds, and >= 95% of the untraced decode rate (ci.sh-gated)
        "traced_overhead_ratio": (
            report["modes"]["continuous_traced"]["traced_overhead_ratio"]),
        "traced_token_parity": (
            report["modes"]["continuous_traced"]["token_parity"]),
        "traced_steady_builds_delta": (
            report["modes"]["continuous_traced"]["steady_builds_delta"]),
        **parity,
    }
    # compile-time profile: the slowest AOT builds across the shared cache
    report["meta"]["slowest_builds"] = aot.top_builds(5)
    report["meta"]["aot_build_s_total"] = round(aot.build_s_total, 3)
    text = json.dumps(report, indent=2)
    print(text)
    if args.json:
        with open(args.json, "w") as fh:
            fh.write(text + "\n")
    return report


if __name__ == "__main__":
    main()
