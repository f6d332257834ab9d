"""Driver of the serving cells: open-loop traffic through ``Router.submit``
/ ``Router.step`` into the continuous-batching ``ServeEngine``, configured
as ``launch/serve.py`` configures it (slotted KV, fused greedy sampling,
no prefix cache, one replica).

Requests come on a schedule whatever the server does.  Each is timed from
when it was due, not from when it was submitted.  A warm-up phase of the
same traffic fills the lanes before the window; the requests due in the
window are drained after it while arrivals go on.  Their latencies all
count; the rate counts the output tokens that reached the host inside the
window.  Once the window's requests are done, the engine is freed and the
plain reference reads a sample of the finished requests, the longest
among them: for every served token, how far its logit lies below the
reference's best at that position.

Traffic keys: ``rate_per_s``, ``warmup_s``, ``drain_s``, ``prompt`` and
``output`` (log-normal ``median``, ``sigma``, ``min``, ``max``),
``lanes``, ``max_len``, ``check_requests``, ``trace_seconds`` (the part
of the window a traced run traces), ``schedule_seed``, ``limits``.
"""
from __future__ import annotations

import time

import numpy as np

from bench import compare, generators, harness, stats, trace_reduce
from bench.kinds.train import weights_key

WARM_RID = 1 << 40      # the shape warm-up's request ids, apart from traffic's


class Server:
    """The program's serving stack for one cell, built once per process;
    ``start`` makes a router with weights from a seed.  The router never
    sheds: in an open loop the queue is part of the latency measured, and a
    shed request would read as failed."""

    def __init__(self, cell: harness.Cell, devices, *, traced: bool = False):
        import jax
        from jax.sharding import AxisType, Mesh

        from repro.models import registry
        from repro.models.common import ShardRules
        from repro.obs import Observer, Tracer
        from repro.serve import EngineConfig
        from repro.train.step import shardings_for

        tr = cell.traffic
        self.cell, self.adapter, self.ref = cell, cell.adapter(), cell.ref()
        self.cfg = self.adapter.arch(cell.config)
        mesh = Mesh(np.array(devices[:1]).reshape(1, 1, 1),
                    ("pod", "data", "model"), axis_types=(AxisType.Auto,) * 3)
        rules = ShardRules.for_mesh(mesh)
        p_sh = shardings_for(mesh, registry.param_pspecs(self.cfg, rules))
        ref, adapter, cfgd = self.ref, self.adapter, cell.config
        self.make_params = jax.jit(
            lambda key: adapter.to_program(ref.init(cfgd, key)),
            out_shardings=p_sh)
        self.engine_config = EngineConfig(
            max_slots=int(tr["lanes"]), max_len=int(tr["max_len"]), seed=0)
        self.obs = Observer(tracer=Tracer(), name="router") if traced else None
        self.mesh, self.rules = mesh, rules

    def start(self, seed: int):
        from repro.serve import Router, RouterConfig

        router = Router(self.cfg, self.mesh, self.rules,
                        self.make_params(weights_key(seed)),
                        self.engine_config,
                        RouterConfig(replicas=1, shed_queue_depth=1 << 30),
                        obs=self.obs)
        router.prebuild()
        return router


def warm_shapes(router, vocab: int) -> None:
    """One request per prefill bucket, so every executable the traffic can
    reach has run once before the clock starts."""
    eng = router.replicas[0].engine
    for i, b in enumerate(eng.buckets):
        plen = min(b, eng.econ.max_len - 2)
        router.submit(np.arange(plen, dtype=np.int32) % vocab,
                      max_new_tokens=2, temperature=0.0, rid=WARM_RID + i)
    router.run()


def drive(router, reqs, seconds: float, *, drain_s: float,
          traced_s: float = 0.0, on_open=None, on_close=None):
    """Submit each request when it falls due and step the router until the
    window's requests are done.  The host's work is annotated ``bench.*``
    in the window's first ``traced_s`` seconds (a trace's window is the
    ``bench.*`` extent) and ``warm.*``, ``window.*`` and ``drain.*``
    elsewhere; ``on_open`` and ``on_close`` run as that part begins and
    ends.  Returns (window start on the host clock, due time of each
    submitted request)."""
    import jax

    annotate = jax.profiler.TraceAnnotation
    due_abs: dict[int, float] = {}
    window_rids = {r.index for r in reqs if r.in_window}
    base = time.perf_counter() - reqs[0].due   # the first request is due now
    i, phase = 0, "warm"
    while True:
        now = time.perf_counter()
        if now < base:
            new = "warm"
        elif now < base + traced_s:
            new = "bench"
        elif now < base + seconds:
            new = "window"
        else:
            new = "drain"
        if new != phase:
            if new == "bench" and on_open is not None:
                on_open()
            if phase == "bench" and on_close is not None:
                on_close()
            phase, now = new, time.perf_counter()
        if phase == "drain" and (window_rids.issubset(router.completions)
                                 or now >= base + seconds + drain_s):
            break
        with annotate(f"{phase}.submit"):
            while i < len(reqs) and base + reqs[i].due <= now:
                r = reqs[i]
                router.submit(r.prompt, max_new_tokens=r.max_new_tokens,
                              temperature=0.0, rid=r.index)
                due_abs[r.index] = base + r.due
                i += 1
        if router.has_work():
            with annotate(f"{phase}.step"):
                router.step()
        elif i < len(reqs):
            with annotate(f"{phase}.idle"):
                time.sleep(max(0.0, min(1e-3, base + reqs[i].due - now)))
        else:
            break
    return base, due_abs


def latencies(router, reqs, base: float, seconds: float, due_abs: dict):
    """TTFT of each window request from when it was due, every gap between
    its consecutive tokens, and output tokens that reached the host in the
    window (any request).  A request that did not end ``ok`` with its full
    budget counts as failed and misses every limit."""
    ttft, itl, failed = [], [], 0
    for r in reqs:
        if not r.in_window:
            continue
        c = router.completions.get(r.index)
        if c is None or c.status != "ok" or len(c.tokens) != r.max_new_tokens:
            failed += 1
            ttft.append(float("inf"))
            itl.append(float("inf"))
            continue
        ttft.append((c.token_times[0] - due_abs[r.index]) * 1e3)
        itl.extend(np.diff(c.token_times) * 1e3)
    streams = [c.token_times for c in router.completions.values()]
    streams += [rec.token_times for rec in router.records.values()]
    in_window = sum(1 for ts in streams for t in ts if base <= t < base + seconds)
    return ttft, itl, in_window, failed


def check_sample(seed: int, reqs, router, k: int) -> list:
    """``k`` finished window requests drawn from the seed, the longest
    among them; (prompt, served tokens) of each."""
    done = [r for r in reqs if r.in_window
            and router.completions.get(r.index) is not None
            and router.completions[r.index].status == "ok"]
    if not done:
        return []
    longest = max(done, key=lambda r: r.prompt.size + r.max_new_tokens)
    rest = [r for r in done if r is not longest]
    pick = generators.rng(seed, 3).permutation(len(rest))[:max(0, k - 1)]
    chosen = [longest] + [rest[j] for j in sorted(pick)]
    return [(r.prompt, np.asarray(router.completions[r.index].tokens, np.int32))
            for r in chosen]


def reference_gaps(cell: harness.Cell, seed: int, sample, *,
                   control: bool = False) -> np.ndarray:
    """Every served token's gap below the float32 reference's best logit
    (or, with ``control``, the gap of the token that the fp8 reference
    puts first).  One compiled forward over ``max_len`` positions serves
    every request: padding after the last position changes nothing before
    it under the causal mask."""
    import jax
    import jax.numpy as jnp

    ref, cfgd = cell.ref(), cell.config
    L = int(cell.traffic["max_len"])
    with jax.default_matmul_precision("highest"):
        w = jax.jit(lambda k: ref.init(cfgd, k))(weights_key(seed))
        f32 = jax.jit(lambda w, t: ref.logits(cfgd, w, t, "f32")[0])
        fp8 = jax.jit(lambda w, t: ref.logits(cfgd, w, t, "fp8")[0])
        gaps = []
        for prompt, served in sample:
            seq = np.concatenate([prompt, served[:-1]])
            toks = np.zeros((1, L), np.int32)
            toks[0, :seq.size] = seq
            pos = slice(prompt.size - 1, seq.size)
            lg = f32(w, jnp.asarray(toks))[pos]
            if control:
                g = compare.control_logit_gaps(lg, fp8(w, jnp.asarray(toks))[pos])
            else:
                g = compare.served_logit_gaps(lg, jnp.asarray(served))
            gaps.append(np.asarray(g))
    return np.concatenate(gaps) if gaps else np.zeros(0)


def run(cell: harness.Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, devices, scratch) -> dict:
    import jax

    tr = cell.traffic
    server = Server(cell, devices, traced=trace)
    router = server.start(seed)
    warm_shapes(router, server.cfg.vocab)
    reqs = generators.serve_requests(seed, tr, server.cfg.vocab, seconds)

    trace_dir = scratch / "trace" if trace else None
    traced_s = min(float(tr["trace_seconds"]), seconds) if trace else 0.0

    def on_open():
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=trace_reduce.profile_options())

    base, due_abs = drive(router, reqs, seconds, drain_s=float(tr["drain_s"]),
                          traced_s=traced_s, on_open=on_open,
                          on_close=jax.profiler.stop_trace)
    ttft, itl, tokens, failed = latencies(router, reqs, base, seconds, due_abs)
    attempted = sum(1 for r in reqs if r.in_window)
    device = harness.device_record(devices)
    sample = check_sample(seed, reqs, router, int(tr["check_requests"]))
    out = {
        "setup_s": base - t_start,
        "e2e": {"ttft_p95_ms": stats.percentile(ttft, 95),
                "itl_p95_ms": float(stats.percentile(itl, 95)),
                "serve_tokens_per_s": tokens / seconds},
        "attempted": attempted, "failed": failed, "device": device,
        "record": {"kind": "serve", "chips": 1, "window_s": seconds,
                   "requests": attempted, "device_kind": device["kind"]},
    }
    if trace:
        spans = trace_reduce.host_spans(server.obs.tracer.events, base,
                                        base + traced_s)
        summary = trace_reduce.reduce_dir(trace_dir)
        out["record"].update(trace=summary, spans=spans)
        out["device"] = {**device, "busy_s": summary["busy_s"],
                         "window_s": summary["window_s"]}
        out["breakdown"] = summary["breakdown"]
    del router, server
    gaps = reference_gaps(cell, seed, sample)
    served = int(gaps.size)
    out["checks"] = [
        harness.Check("logit_gap", float(gaps.max()) if served else float("nan"),
                      float(tr["limits"]["logit_gap"])),
    ]
    return out
