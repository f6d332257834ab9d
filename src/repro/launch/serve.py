"""Serving launcher.

Continuous-batching engine(s) behind the router front-end under a
Poisson request stream (the default):

    python -m repro.launch.serve --arch smollm-360m --smoke \
        --requests 16 --rate 20 --max-slots 8

Multi-replica serving with crash failover, load shedding, and
zero-downtime drain (serve/router.py):

    python -m repro.launch.serve --arch smollm-360m --smoke \
        --requests 32 --replicas 3 --drain-at 8 --shed-queue-depth 16

The engine serves every slot-capable family — lm KV caches and the
recurrent state kinds alike (xlstm's per-lane recurrent state, zamba's
composed hybrid cache):

    python -m repro.launch.serve --arch xlstm-1.3b --smoke --requests 8
    python -m repro.launch.serve --arch zamba2-1.2b --smoke --requests 8

The paged-layout knobs (--kv-layout paged, --prefill-chunk,
--prefix-cache, --admission preempt) are KV-only: recurrent state is
O(1) in sequence length, so there is no seq axis to page.

Legacy static batch (one fixed batch to completion):

    python -m repro.launch.serve --arch gemma2-27b --smoke --engine static
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import local_mesh, make_production_mesh, single_device_mesh
from repro.models import registry
from repro.models.common import ShardRules
from repro.obs import Observer, merged_histogram, to_chrome_trace, validate
from repro.serve import (
    ENGINE_FAULT_SITES, REPLICA_FAULT_SITES, STATUSES, EngineConfig,
    FaultPlan, Router, RouterConfig, ServeConfig, generate_static,
)


def run_static(cfg, mesh, rules, params, args, rng):
    prompts = rng.integers(0, cfg.vocab,
                           (args.batch, args.prompt_len)).astype(np.int32)
    extra = None
    if cfg.family == "vlm":
        extra = rng.normal(size=(args.batch, cfg.frontend_tokens,
                                 cfg.frontend_dim)).astype(np.float32)
    if cfg.family == "audio":
        extra = rng.normal(size=(args.batch, cfg.enc_seq,
                                 cfg.d_model)).astype(np.float32)
    out = generate_static(cfg, mesh, rules, params, prompts, extra,
                          ServeConfig(max_new_tokens=args.new_tokens,
                                      temperature=args.temperature))
    for i, row in enumerate(out):
        print(f"seq{i}: {row.tolist()}")


def _print_latency_summary(router):
    """Per-status latency table: p50/p99 time-to-first-token and
    per-token latency, one row per terminal status that occurred.

    Consumes the shared ``ttft_ms_<status>`` / ``tpot_ms_<status>``
    histograms (obs/metrics.py) merged across the router registry and
    every replica engine's registry — the same mergeable sketches the
    bench snapshot embeds, not a hand-rolled percentile pass over raw
    completion timestamps."""
    regs = [router.obs.metrics] + [h.engine.obs.metrics
                                   for h in router.replicas]
    rs = router.stats
    print("-- latency by status (p50/p99 ms):")
    for status in STATUSES:
        n = rs.get(f"status_{status}", 0)
        if not n:
            continue
        ttft = merged_histogram(f"ttft_ms_{status}", regs)
        tpot = merged_histogram(f"tpot_ms_{status}", regs)
        fmt = lambda h: (f"{h.quantile(0.50):8.1f}/{h.quantile(0.99):8.1f}"
                         if h.count else "       -/       -")
        print(f"   {status:9s} n={n:4d}  "
              f"ttft {fmt(ttft)}  per-token {fmt(tpot)}")


def run_stream(cfg, mesh, rules, params, args, rng):
    """Drive N engine replicas behind the router front-end with a
    Poisson arrival trace (``--replicas 1`` is a plain engine with the
    router's admission queue in front)."""
    kind = registry.state_kind(cfg)
    if args.kv_layout == "paged" and kind != "kv":
        raise SystemExit(
            f"--kv-layout paged: family {cfg.family!r} has state kind "
            f"{kind!r} — recurrent state has no seq axis to page; "
            "drop the flag to serve on the slotted layout")
    if args.drain_at is not None and args.replicas < 2:
        raise SystemExit("--drain-at needs --replicas >= 2 (draining the "
                         "only replica leaves nothing to migrate onto)")
    max_len = args.prompt_len + args.new_tokens + 8
    if args.kv_layout == "paged":
        max_len = -(-max_len // args.page_size) * args.page_size
    faults = None
    if args.replica_chaos_rate > 0:
        faults = FaultPlan(
            args.chaos_seed,
            {site: args.replica_chaos_rate for site in REPLICA_FAULT_SITES})
    engine_faults = None
    if args.chaos_rate > 0:
        engine_faults = [
            FaultPlan(args.chaos_seed + 1 + i,
                      {site: args.chaos_rate for site in ENGINE_FAULT_SITES})
            for i in range(args.replicas)
        ]
    draft_params = None
    if args.spec_k > 0:
        # a same-architecture draft nudged away from the target: cheap to
        # stand up and accepts often enough to demo multi-token commits
        # (real deployments pass trained draft weights)
        mod = registry.get_module(cfg)
        noise = mod.init(cfg, jax.random.PRNGKey(args.seed + 1))
        a = args.spec_draft_alpha
        draft_params = jax.tree.map(lambda p, n: (1 - a) * p + a * n,
                                    params, noise)
    obs = None
    if args.trace_out or args.flightrec_dir:
        # full flight: tracer + ring-buffer recorder (invariant failures
        # dump to --flightrec-dir); metrics are always on either way
        obs = Observer.full(dump_dir=args.flightrec_dir or ".",
                            name="router")
    router = Router(
        cfg, mesh, rules, params,
        EngineConfig(
            max_slots=args.max_slots,
            max_len=max_len,
            seed=args.seed,
            kv_layout=args.kv_layout,
            page_size=args.page_size,
            num_blocks=args.num_blocks,
            prefill_chunk=args.prefill_chunk,
            prefix_cache=args.prefix_cache,
            admission=args.admission,
            max_retries=args.max_retries,
            spec_draft=cfg if args.spec_k > 0 else None,
            spec_k=args.spec_k,
        ),
        RouterConfig(replicas=args.replicas,
                     shed_queue_depth=args.shed_queue_depth),
        faults=faults,
        engine_faults=engine_faults,
        obs=obs,
        draft_params=draft_params,
    )
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.requests))
    prompts = [
        rng.integers(0, cfg.vocab, rng.integers(2, args.prompt_len + 1))
        .astype(np.int32)
        for _ in range(args.requests)
    ]
    budgets = rng.integers(1, args.new_tokens + 1, args.requests)

    t0 = time.perf_counter()
    i = 0
    drained = False
    while i < len(prompts) or router.has_work():
        now = time.perf_counter() - t0
        while i < len(prompts) and arrivals[i] <= now:
            router.submit(prompts[i], max_new_tokens=int(budgets[i]),
                          temperature=args.temperature, rid=i,
                          deadline_s=args.deadline_s)
            i += 1
        if (args.drain_at is not None and not drained
                and len(router.completions) >= args.drain_at):
            idx = args.replicas - 1
            moved = router.drain(idx)
            print(f"-- drained replica {idx}: migrated {moved} in-flight "
                  "requests to survivors")
            drained = True
        if not router.step() and i < len(prompts):
            time.sleep(max(0.0, t0 + arrivals[i] - time.perf_counter()))
    wall = time.perf_counter() - t0

    tokens = 0
    for rid in range(len(prompts)):
        c = router.completions[rid]
        tokens += len(c.tokens)
        lat = (f"{(c.finish_time - c.submit_time) / len(c.tokens) * 1e3:.1f}"
               " ms/tok" if c.tokens else "-")
        note = f"  [{c.error}]" if c.error else ""
        where = router.placements.get(rid)
        place = f"r{where}" if where is not None else "--"
        print(f"req{rid}: {c.status:9s} {place} plen={c.prompt_len} "
              f"new={len(c.tokens)} {lat}  {c.tokens}{note}")
    print(f"-- {tokens} tokens in {wall:.2f}s = {tokens / wall:.1f} tok/s "
          f"across {args.replicas} replica(s)")
    for h in router.replicas:
        s = h.engine.stats
        line = (f"-- replica {h.idx} [{h.state}] "
                f"state[{s['state_kind']}/{args.kv_layout}]: "
                f"{s['kv_peak_used_bytes'] / 2**20:.2f} MiB peak used / "
                f"{h.engine.kv_reserved_bytes / 2**20:.2f} MiB reserved")
        if args.kv_layout == "paged":
            line += (f"  prefix hit_rate {s['prefix_hit_rate']:.2f} "
                     f"preempt {s['preemptions']} resume {s['resumed']}")
        if args.spec_k > 0:
            line += (f"  spec accept {s['spec_acceptance_rate']:.2f} "
                     f"tok/round {s['tokens_per_decode_dispatch']:.2f}")
        print(line)
    rs = router.stats
    print(f"-- status: ok {rs['status_ok']} timeout {rs['status_timeout']} "
          f"cancelled {rs['status_cancelled']} failed {rs['status_failed']} "
          f"shed {rs['status_shed']}  "
          f"failovers {rs['failovers']} migrated {rs['migrated']}")
    if faults is not None or engine_faults is not None:
        injected = sum(h.engine.stats["faults_injected"]
                       for h in router.replicas)
        print(f"-- chaos[seed {args.chaos_seed}]: engine faults {injected}  "
              f"replicas dead {rs['replicas_dead']} "
              f"stalls {rs['stalls_injected']}/{rs['stalls_detected']} "
              f"(injected/detected)")
    _print_latency_summary(router)
    if args.trace_out:
        ev = router.obs.tracer.events
        info = validate(ev)
        to_chrome_trace(ev, args.trace_out)
        print(f"-- trace: {info['events']} events / {info['spans']} spans / "
              f"{info['requests']} requests -> {args.trace_out} "
              "(load in chrome://tracing or ui.perfetto.dev)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", choices=("production", "local", "single"),
                    default="single")
    ap.add_argument("--engine", choices=("continuous", "static"),
                    default="continuous")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    # request-stream knobs (continuous engine)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=20.0,
                    help="Poisson arrival rate, requests/s")
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    # router front-end knobs (continuous engine)
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the router (shared AOT "
                         "cache + weights; crash failover between them)")
    ap.add_argument("--shed-queue-depth", type=int, default=64,
                    help="bounded admission queue: submissions beyond "
                         "this depth terminate with status 'shed'")
    ap.add_argument("--drain-at", type=int, default=None,
                    help="after this many completions, drain the last "
                         "replica (zero-downtime migration to survivors); "
                         "needs --replicas >= 2")
    # KV layout knobs (continuous engine)
    ap.add_argument("--kv-layout", choices=("slotted", "paged"),
                    default="slotted")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV block size (paged layout)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="KV pool size in blocks (paged; default worst case)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help=">0: admit prompts in chunks of this many tokens "
                         "interleaved with decode (paged only)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="refcounted shared-prefix block reuse (paged): "
                         "repeated prompt prefixes skip prefill")
    # robustness knobs (continuous engine)
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request TTL from submission; expired requests "
                         "finish with status 'timeout' keeping emitted "
                         "tokens")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="bounded retries (preempt-and-replay) before a "
                         "faulting request terminates 'failed'")
    ap.add_argument("--chaos-rate", type=float, default=0.0,
                    help=">0: inject seeded faults at every per-engine "
                         "fault site with this per-consult probability "
                         "(exercises quarantine + retry recovery)")
    ap.add_argument("--replica-chaos-rate", type=float, default=0.0,
                    help=">0: inject seeded replica crashes/stalls at "
                         "this per-tick probability (exercises router "
                         "failover; pair with --replicas >= 2)")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="FaultPlan seed (reproducible fault schedules)")
    # speculative decoding knobs (continuous engine)
    ap.add_argument("--spec-k", type=int, default=0,
                    help=">0: speculative decoding — a draft model "
                         "proposes this many tokens per lane, all "
                         "verified in one fused target dispatch; greedy "
                         "output is bitwise-unchanged")
    ap.add_argument("--spec-draft-alpha", type=float, default=0.1,
                    help="demo draft weights = (1-a)*target + a*fresh "
                         "init; smaller a = higher acceptance")
    # observability knobs (continuous engine)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the request/engine span timeline as a "
                         "Chrome-trace JSON (chrome://tracing, "
                         "ui.perfetto.dev)")
    ap.add_argument("--flightrec-dir", default=None, metavar="DIR",
                    help="arm the flight recorder: invariant failures "
                         "dump the last N events as flightrec_*.json "
                         "into this directory")
    ap.add_argument("--admission", choices=("deficit", "preempt"),
                    default="deficit",
                    help="deficit: gate admission on worst-case block "
                         "commitments; preempt: run the pool near full and "
                         "evict-and-requeue the lowest-priority lane when "
                         "decode growth finds it empty (paged only)")
    args = ap.parse_args()
    enable_compile_cache()

    mesh = {"production": make_production_mesh,
            "local": local_mesh,
            "single": single_device_mesh}[args.mesh]()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    rules = ShardRules.for_mesh(mesh)
    mod = registry.get_module(cfg)
    params = mod.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(args.seed)

    if args.engine == "continuous" and registry.supports_slot_serving(cfg):
        run_stream(cfg, mesh, rules, params, args, rng)
    else:
        if args.engine == "continuous":
            print(f"# family {cfg.family!r} has no slot-serving support; "
                  "falling back to the static loop")
        run_static(cfg, mesh, rules, params, args, rng)


if __name__ == "__main__":
    main()
