"""Observability layer: metrics registry, structured tracing, flight
recorder, and their wiring through the serve engine, router, AOT cache,
and train loop.

Coverage:
- histogram quantile accuracy vs exact percentiles (log-bucket sketches
  carry a bounded relative error) and merge == pooled-samples identity;
- counter/gauge semantics behind the ``MetricMap`` facade (monotone
  counters, absolute-set gauges, kind-mixing rejected);
- trace schema validation over real engine drives (preempt-and-requeue)
  and a router drive with a replica kill (failover) — every request's
  lifecycle starts at ``submit`` and ends at exactly one ``terminal``;
- an induced invariant failure dumps the flight recorder, and the
  failing request's full span timeline reconstructs from the dump alone;
- tracing is behavior-invisible: the same fuzz stream driven with the
  observer fully armed (fake clock shared engine<->tracer) is bitwise
  token-identical to the untraced drive with zero new executable builds;
- ``AotCache`` per-key build timing and the slowest-builds report;
- an armed tracer mirrors its spans into the JAX profiler as nested
  ``repro.<name>`` host annotations (request spans stay out), and records
  each span's parent; the engine's and router's host phases nest under
  ``engine.step``, and each admitted request has one ``queue_wait``.
"""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.aot import AotCache
from repro.launch.mesh import _mk, single_device_mesh
from repro.models import registry
from repro.models.common import ShardRules
from repro.obs import (
    FlightRecorder, MetricMap, MetricsRegistry, Observer, Tracer,
    load_jsonl, merged_histogram, request_timeline, request_track,
    to_chrome_trace, to_jsonl, validate,
)
from repro.serve import EngineConfig, ServeEngine
from repro.serve.router import Router, RouterConfig

from test_engine_fuzz import make_stream

MAX_SLOTS, MAX_LEN = 3, 48
SLOTTED = EngineConfig(max_slots=MAX_SLOTS, max_len=MAX_LEN)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture(scope="module")
def setup():
    mesh = single_device_mesh()
    rules = ShardRules.for_mesh(mesh)
    cfg = dataclasses.replace(
        get_smoke_config("smollm-360m"), compute_dtype="float32")
    params = registry.get_module(cfg).init(cfg, jax.random.PRNGKey(0))
    aot = AotCache("obs-test")
    ServeEngine(cfg, mesh, rules, params, SLOTTED, aot=aot).prebuild()
    return cfg, mesh, rules, params, aot


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_counter_monotone_gauge_absolute():
    reg = MetricsRegistry("t")
    c = reg.counter("hits")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError):
        c.set(2)                       # counters never go backwards
    g = reg.gauge("depth")
    g.set(7)
    g.set(2)                           # gauges do
    assert g.value == 2
    g.set_max(5)
    g.set_max(3)                       # peak semantics
    assert g.value == 5
    reg.check()


def test_kind_mixing_rejected():
    reg = MetricsRegistry("t")
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    with pytest.raises(TypeError):
        reg.histogram("x")
    assert reg.kind("x") == "counter"
    assert reg.kind("nope") is None


def test_histogram_quantiles_match_exact_within_bucket_error():
    """The log-bucket sketch (growth 2**(1/4)) must land within ~10%
    relative error of exact percentiles on a heavy-tailed sample."""
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=1.0, sigma=1.2, size=5000)
    reg = MetricsRegistry("t")
    h = reg.histogram("lat")
    for x in xs:
        h.observe(float(x))
    for q in (0.50, 0.90, 0.99):
        exact = float(np.percentile(xs, 100 * q))
        approx = h.quantile(q)
        assert abs(approx - exact) / exact < 0.10, \
            f"p{int(q * 100)}: sketch {approx:.3f} vs exact {exact:.3f}"
    assert h.min == pytest.approx(xs.min())
    assert h.max == pytest.approx(xs.max())
    assert h.mean == pytest.approx(xs.mean(), rel=1e-6)
    with pytest.raises(ValueError):
        h.observe(-1.0)
    with pytest.raises(ValueError):
        h.observe(float("nan"))


def test_histogram_merge_equals_pooled_samples():
    rng = np.random.default_rng(1)
    a, b = rng.exponential(5.0, 400), rng.exponential(50.0, 300)
    regs = [MetricsRegistry(f"r{i}") for i in range(3)]
    for x in a:
        regs[0].histogram("lat").observe(float(x))
    for x in b:
        regs[1].histogram("lat").observe(float(x))
    # regs[2] never observed "lat": merged_histogram must skip it
    merged = merged_histogram("lat", regs)
    pooled = MetricsRegistry("p").histogram("lat")
    for x in np.concatenate([a, b]):
        pooled.observe(float(x))
    assert merged.count == pooled.count == 700
    assert merged.buckets == pooled.buckets
    for q in (0.5, 0.9, 0.99):
        assert merged.quantile(q) == pooled.quantile(q)


def test_metricmap_facade_over_registry():
    reg = MetricsRegistry("t")
    m = MetricMap(reg, ("a", "b", "peak"), gauges=("peak",))
    m["a"] += 1
    m["a"] += 2
    m["b"] += 5
    m["peak"] = 10
    m["peak"] = 4                      # gauge: absolute set allowed
    assert m["a"] == 3 and m["peak"] == 4
    assert dict(m) == {"a": 3, "b": 5, "peak": 4}
    assert m.copy() == dict(m)
    assert m.get("nope", 0) == 0
    with pytest.raises(ValueError):
        m["b"] = 1                     # counter: decrease rejected
    with pytest.raises(TypeError):
        del m["a"]
    # the facade's values live in the registry (same snapshot source)
    snap = reg.snapshot()
    assert snap["a"] == {"kind": "counter", "value": 3}
    assert snap["peak"] == {"kind": "gauge", "value": 4}
    reg.check()


# ---------------------------------------------------------------------------
# Tracer + flight recorder units
# ---------------------------------------------------------------------------


def test_tracer_spans_balance_and_export(tmp_path):
    clock = FakeClock()
    tr = Tracer(clock)
    tr.mark("submit", 0, plen=4)
    clock.t = 1.0
    with tr.span("decode", track="engine", lanes=2):
        clock.t = 2.0
        tr.mark("first_token", 0)
    tr.mark("terminal", 0, status="ok")
    info = validate(tr.events)
    assert info == {"events": 5, "spans": 1, "requests": 1, "terminals": 1}
    assert [e["name"] for e in request_timeline(tr.events, 0)] \
        == ["submit", "first_token", "terminal"]

    p = to_jsonl(tr.events, str(tmp_path / "t.jsonl"))
    assert load_jsonl(p) == tr.events
    doc = to_chrome_trace(tr.events, str(tmp_path / "t.json"))
    rows = doc["traceEvents"]
    assert json.load(open(tmp_path / "t.json")) == doc
    # spans on the track tid, request instants on tid 1000+rid, ts in us
    b = next(r for r in rows if r["ph"] == "B")
    assert b["ts"] == pytest.approx(1e6)
    assert {r["tid"] for r in rows if r["ph"] == "i"} == {1000}
    assert any(r["ph"] == "M" and r["args"]["name"] == "request 0"
               for r in rows)


def test_validate_rejects_malformed_streams():
    tr = Tracer(FakeClock())
    sid = tr.begin("decode")
    with pytest.raises(AssertionError):
        validate(tr.events)            # span left open
    tr.end(sid)
    validate(tr.events)

    tr2 = Tracer(FakeClock())
    tr2.mark("admit", 3)               # lifecycle not starting at submit
    with pytest.raises(AssertionError):
        validate(tr2.events)

    tr3 = Tracer(FakeClock())
    tr3.mark("submit", 1)
    tr3.mark("terminal", 1, status="ok")
    tr3.mark("decode", 1)              # event after terminal
    with pytest.raises(AssertionError):
        validate(tr3.events)


def test_span_parents_recorded_checked_and_exported(tmp_path):
    tr = Tracer(FakeClock())
    with tr.span("engine.step", track="e", step_num=4):
        with tr.span("decode", track="e"):
            with tr.span("decode.fetch", track="e"):
                pass
        with tr.span("other", track="f"):      # another track: no parent
            pass
    begins = {e["name"]: e for e in tr.events if e["ph"] == "B"}
    assert "parent" not in begins["engine.step"]
    assert begins["decode"]["parent"] == begins["engine.step"]["sid"]
    assert begins["decode.fetch"]["parent"] == begins["decode"]["sid"]
    assert "parent" not in begins["other"]
    assert begins["engine.step"]["args"] == {"step_num": 4}
    validate(tr.events)
    rows = to_chrome_trace(tr.events)["traceEvents"]
    fetch = next(r for r in rows if r["name"] == "decode.fetch"
                 and r["ph"] == "B")
    assert fetch["args"]["parent"] == begins["decode"]["sid"]

    bad = [dict(e) for e in tr.events]
    bad[1]["parent"] = 99                      # names a span never opened
    with pytest.raises(AssertionError, match="parent"):
        validate(bad)


def test_armed_tracer_mirrors_spans_into_profiler(tmp_path):
    from jax.profiler import ProfileData

    tr = Tracer()
    unarmed = Observer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("router.step", track="router"):
            with tr.span("engine.step", track="replica0", step_num=7):
                with tr.span("decode", track="replica0"):
                    jax.numpy.ones(4).block_until_ready()
            tr.mark("submit", 3)
            sid = tr.begin("queue_wait", cat="request",
                           track=request_track(3), rid=3)
            tr.end(sid)
        with unarmed.span("ghost"):
            pass
        unarmed.end(unarmed.begin("ghost2"))
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    host = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.") or "ghost" in e.name]
    spans = {name: (s, e) for s, e, name in host}
    assert sorted(spans) == ["repro.decode", "repro.engine.step",
                             "repro.router.step"]
    assert len(host) == 3          # no queue_wait, nothing from unarmed

    def inside(child, parent):
        return spans[parent][0] <= spans[child][0] \
            and spans[child][1] <= spans[parent][1]

    assert inside("repro.decode", "repro.engine.step")
    assert inside("repro.engine.step", "repro.router.step")
    assert tr._mirrors == {}                    # every annotation closed


def test_flight_recorder_ring_bounds_and_dump(tmp_path):
    clock = FakeClock()
    rec = FlightRecorder(capacity=8, clock=clock, dump_dir=str(tmp_path))
    for i in range(20):
        rec.record("tick", i=i)
    assert len(rec.events()) == 8
    assert rec.recorded == 20 and rec.dropped == 12
    assert [e["args"]["i"] for e in rec.events()] == list(range(12, 20))
    assert all(e["seq"] == 12 + j for j, e in enumerate(rec.events()))
    path = rec.dump("test_reason", context={"k": "v"})
    doc = json.load(open(path))
    assert doc["reason"] == "test_reason" and doc["context"] == {"k": "v"}
    assert doc["recorded"] == 20 and doc["dropped"] == 12
    assert len(doc["events"]) == 8
    assert rec.dumps == 1 and rec.last_dump == path


def test_flight_recorder_dump_names_never_collide(tmp_path):
    """Dump names come from scanning the directory, not a per-recorder
    counter: two recorders sharing a dump_dir (several engines, or a
    re-launched process after a crash) must never overwrite each other's
    dump 000 — the one artifact written because something went wrong."""
    a = FlightRecorder(capacity=4, clock=FakeClock(), dump_dir=str(tmp_path))
    b = FlightRecorder(capacity=4, clock=FakeClock(), dump_dir=str(tmp_path))
    a.record("from_a")
    b.record("from_b")
    paths = [a.dump("a0"), b.dump("b0"), a.dump("a1")]
    assert len(set(paths)) == 3, f"dump paths collided: {paths}"
    # every dump is still on disk with its own reason — nothing clobbered
    reasons = {json.load(open(p))["reason"] for p in paths}
    assert reasons == {"a0", "b0", "a1"}
    # a recorder in a fresh process (new instance, pre-existing dumps)
    # resumes after the highest existing index, gaps and all
    (tmp_path / "flightrec_041.json").write_text("{}")
    c = FlightRecorder(capacity=4, clock=FakeClock(), dump_dir=str(tmp_path))
    assert c.dump("c0").endswith("flightrec_042.json")


def test_observer_child_isolates_metrics_shares_timeline():
    obs = Observer.full(clock=FakeClock(), name="router")
    c0, c1 = obs.child("replica0"), obs.child("replica1")
    c0.metrics.counter("decode_steps").inc()
    c1.metrics.counter("decode_steps").inc(5)
    assert c0.metrics.counter("decode_steps").value == 1
    assert c1.metrics.counter("decode_steps").value == 5
    c0.mark("submit", 0, track=c0.name)
    c1.mark("submit", 1, track=c1.name)
    assert len(obs.tracer.events) == 2          # one shared timeline
    # tracer events flow into the recorder ring via the sink
    assert len(obs.recorder.events()) == 2


# ---------------------------------------------------------------------------
# Engine trace schema (preempt-and-requeue drive)
# ---------------------------------------------------------------------------


def test_engine_trace_schema_with_preempt(setup, tmp_path):
    cfg, mesh, rules, params, aot = setup
    clock = FakeClock()
    obs = Observer.full(clock=clock, name="engine")
    eng = ServeEngine(cfg, mesh, rules, params, SLOTTED, aot=aot,
                      obs=obs, clock=clock)
    rng = np.random.default_rng(0)
    rids = [eng.submit(rng.integers(0, cfg.vocab, 6).astype(np.int32),
                       max_new_tokens=4)
            for _ in range(2 * MAX_SLOTS + 1)]
    tick = 0
    preempted_rid = None
    while eng.has_work():
        eng.step()
        eng.check_invariants()
        if tick == 1 and eng.slots[0] is not None:
            preempted_rid = eng.slots[0].rid
            eng.preempt(0)
        clock.t += 1.0
        tick += 1
        assert tick < 200

    info = validate(obs.tracer.events)
    assert info["requests"] == len(rids)
    assert info["terminals"] == len(rids)       # drained: all terminal
    assert info["spans"] > 0                    # decode/prefill spans ran
    # the engine's stream survives both exports: JSONL round trip, and a
    # non-empty Chrome trace
    p = to_jsonl(obs.tracer.events, str(tmp_path / "engine.jsonl"))
    assert validate(load_jsonl(p)) == info
    doc = to_chrome_trace(obs.tracer.events, str(tmp_path / "engine.json"))
    assert doc["traceEvents"]
    assert json.load(open(tmp_path / "engine.json")) == doc
    for rid in rids:
        names = [e["name"] for e in request_timeline(obs.tracer.events, rid)]
        assert names[0] == "submit" and names[-1] == "terminal"
        assert "admit" in names and "first_token" in names
    assert preempted_rid is not None
    names = [e["name"]
             for e in request_timeline(obs.tracer.events, preempted_rid)]
    assert "preempt" in names                   # and it still went terminal
    # ttft/tpot histograms populated for the ok status
    assert obs.metrics.histogram("ttft_ms_ok").count == len(rids)
    assert obs.metrics.histogram("tpot_ms_ok").count == len(rids)
    assert eng.counters["preemptions"] >= 1


def test_trace_zero_cost_when_disabled(setup):
    """No observer: the engine still counts (metrics are always live)
    but emits no events anywhere."""
    cfg, mesh, rules, params, aot = setup
    eng = ServeEngine(cfg, mesh, rules, params, SLOTTED, aot=aot)
    assert eng.obs.tracer is None and eng.obs.recorder is None
    eng.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=2)
    while eng.has_work():
        eng.step()
    assert eng.counters["decode_steps"] > 0
    assert eng.obs.metrics.histogram("ttft_ms_ok").count == 1
    assert eng.obs.dump("nothing") is None      # no recorder: no-op


# ---------------------------------------------------------------------------
# Flight-recorder dump on an induced invariant failure
# ---------------------------------------------------------------------------


def test_invariant_failure_dumps_flight_recorder(setup, tmp_path):
    cfg, mesh, rules, params, aot = setup
    clock = FakeClock()
    obs = Observer.full(clock=clock, dump_dir=str(tmp_path), name="engine")
    eng = ServeEngine(cfg, mesh, rules, params, SLOTTED, aot=aot,
                      obs=obs, clock=clock)
    rid = eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=3)
    while eng.has_work():
        eng.step()
        eng.check_invariants()
        clock.t += 1.0
    # corrupt a status counter (monotone: upward is permitted by the
    # metric layer, caught by the conservation sweep)
    eng.counters["status_failed"] += 1
    with pytest.raises(AssertionError, match="status counters"):
        eng.check_invariants()
    assert obs.recorder.dumps == 1
    doc = json.load(open(obs.recorder.last_dump))
    assert doc["reason"] == "engine_invariant_failure"
    assert "status counters" in doc["context"]["error"]
    assert doc["context"]["counters"]["status_failed"] == 1
    # the failing request's full timeline reconstructs from the dump alone
    names = [e["name"] for e in request_timeline(doc["events"], rid)]
    assert names[0] == "submit" and names[-1] == "terminal"
    assert "admit" in names and "first_token" in names


# ---------------------------------------------------------------------------
# Tracing is behavior-invisible (fuzz stream, fake clock, builds-flat)
# ---------------------------------------------------------------------------


def test_traced_drive_is_bitwise_and_builds_flat(setup):
    cfg, mesh, rules, params, aot = setup

    def drive(obs):
        clock = FakeClock()
        if obs is not None:
            obs.tracer.clock = clock            # one clock, both views
        eng = ServeEngine(cfg, mesh, rules, params, SLOTTED, aot=aot,
                          obs=obs, clock=clock)
        stream = make_stream(np.random.default_rng(31337), cfg.vocab)
        i, tick = 0, 0
        while i < len(stream) or eng.has_work():
            while i < len(stream) and stream[i][0] <= tick:
                _, prompt, budget = stream[i]
                eng.submit(prompt, max_new_tokens=budget, rid=i)
                i += 1
            eng.step()
            eng.check_invariants()
            clock.t += 1.0
            tick += 1
            assert tick < 2000
        return ([list(eng.completions[r].tokens) for r in range(len(stream))],
                eng.counters["decode_steps"])

    builds0 = aot.stats["builds"]
    want = drive(None)
    obs = Observer.full(clock=FakeClock(), name="engine")
    got = drive(obs)
    assert got == want, "arming the observer changed greedy tokens or the " \
        "number of decode steps"
    assert aot.stats["builds"] == builds0, \
        "tracing forced fresh executable builds"
    # the armed observer's metrics snapshot counts the same decode steps
    assert obs.metrics.snapshot()["decode_steps"]["value"] == got[1] > 0
    validate(obs.tracer.events)
    _check_host_phases(obs.tracer.events)


def _check_host_phases(events):
    """Every ``decode`` and ``prefill_chunk`` has an ``engine.step``
    among its ancestors; ``decode.dispatch`` and ``decode.fetch`` are the
    children of a ``decode``; each admitted request has exactly one
    ``queue_wait``, ended at or before its first admission."""
    begins = {e["sid"]: e for e in events if e["ph"] == "B"}

    def ancestors(ev):
        while "parent" in ev:
            ev = begins[ev["parent"]]
            yield ev["name"]

    names = [e["name"] for e in begins.values()]
    for phase in ("engine.step", "engine.admit", "decode", "decode.dispatch",
                  "decode.fetch", "engine.advance", "prefill_chunk"):
        assert phase in names, phase
    for ev in begins.values():
        if ev["name"] in ("decode", "prefill_chunk", "engine.admit",
                          "engine.advance"):
            assert "engine.step" in ancestors(ev), ev
        if ev["name"] in ("decode.dispatch", "decode.fetch"):
            assert begins[ev["parent"]]["name"] == "decode", ev
    waits = [e["rid"] for e in begins.values() if e["name"] == "queue_wait"]
    admitted = {e["rid"] for e in events if e["name"] == "admit"}
    assert sorted(waits) == sorted(admitted)
    for e in events:
        if e["name"] == "queue_wait" and e["ph"] == "E":
            assert e["args"] == {"admitted": True}


def test_router_traced_drive_host_phases_and_parity(setup):
    """The router's drive with the observer armed: router.step holds the
    replica's engine.step (another track), every request waits once, and
    tokens and decode steps match the untraced drive bitwise."""
    cfg, mesh, rules, params, aot = setup

    def drive(obs):
        clock = FakeClock()
        if obs is not None:
            obs.tracer.clock = clock
        router = Router(cfg, mesh, rules, params, SLOTTED,
                        RouterConfig(replicas=1, shed_queue_depth=10_000),
                        aot=aot, clock=clock, obs=obs)
        stream = make_stream(np.random.default_rng(7), cfg.vocab)
        i, tick = 0, 0
        while i < len(stream) or router.has_work():
            while i < len(stream) and stream[i][0] <= tick:
                _, prompt, budget = stream[i]
                router.submit(prompt, max_new_tokens=budget, rid=i)
                i += 1
            router.step()
            clock.t += 1.0
            tick += 1
            assert tick < 2000
        steps = router.replicas[0].engine.counters["decode_steps"]
        return [list(router.completions[r].tokens)
                for r in range(len(stream))], steps

    want = drive(None)
    obs = Observer.full(clock=FakeClock(), name="router")
    got = drive(obs)
    assert got == want
    info = validate(obs.tracer.events)
    assert info["terminals"] == len(want[0])
    _check_host_phases(obs.tracer.events)
    ticks = [e for e in obs.tracer.events
             if e["ph"] == "B" and e["name"] == "router.step"]
    steps = [e for e in obs.tracer.events
             if e["ph"] == "B" and e["name"] == "engine.step"]
    assert ticks and all(e["track"] == "router" for e in ticks)
    assert len(steps) <= len(ticks)
    assert {e["track"] for e in steps} == {"replica0"}


# ---------------------------------------------------------------------------
# Router trace: failover + one terminal per request fleet-wide
# ---------------------------------------------------------------------------


def test_router_trace_failover_single_terminal(setup):
    cfg, mesh, rules, params, aot = setup
    clock = FakeClock()
    obs = Observer.full(clock=clock, name="router")
    router = Router(
        cfg, mesh, rules, params, SLOTTED,
        RouterConfig(replicas=2, shed_queue_depth=10_000),
        aot=aot, clock=clock, obs=obs)
    rng = np.random.default_rng(2)
    n = 6
    for i in range(n):
        router.submit(rng.integers(0, cfg.vocab, 6).astype(np.int32),
                      max_new_tokens=4, rid=i)
    tick = 0
    while router.has_work():
        router.step()
        router.check_invariants()
        if tick == 1:
            router.kill(1)             # strand replica 1's in-flight work
        clock.t += 1.0
        tick += 1
        assert tick < 500

    info = validate(obs.tracer.events)
    assert info["requests"] == n
    # exactly one terminal per rid fleet-wide, even across the failover
    assert info["terminals"] == n
    assert router.counters["failovers"] > 0
    failover_rids = {e["rid"] for e in obs.tracer.events
                     if e.get("cat") == "request"
                     and e["name"] == "failover"}
    assert failover_rids, "kill stranded nothing — failover gate vacuous"
    for rid in failover_rids:
        names = [e["name"] for e in request_timeline(obs.tracer.events, rid)]
        # route (router) precedes failover precedes the terminal
        assert names.index("route") < names.index("failover") \
            < names.index("terminal")
    # replica registries stay isolated; fleet latency merges cleanly
    regs = [router.obs.metrics] + [h.engine.obs.metrics
                                   for h in router.replicas]
    merged = merged_histogram("ttft_ms_ok", regs)
    assert merged.count == sum(
        1 for c in router.completions.values() if c.status == "ok")


# ---------------------------------------------------------------------------
# AotCache build profiling
# ---------------------------------------------------------------------------


def test_aot_build_timing_and_top_builds():
    obs = Observer.full(clock=FakeClock(), name="aot")
    aot = AotCache("t", obs=obs)
    aot.get("slow", lambda: sum(range(200_000)))
    aot.get("fast", lambda: 1)
    aot.get("slow", lambda: 1)                  # hit: no re-time
    assert aot.stats == {"builds": 2, "cache_hits": 1}
    assert set(aot.build_seconds) == {"slow", "fast"}
    assert all(s >= 0.0 for s in aot.build_seconds.values())
    # each miss emitted one balanced aot_build span on the cache's track
    info = validate(obs.tracer.events)
    assert info["spans"] == 2
    assert all(e["name"] == "aot_build" and e["track"] == "t"
               for e in obs.tracer.events)


# ---------------------------------------------------------------------------
# Train loop profiling
# ---------------------------------------------------------------------------


def test_train_loop_traced_smoke():
    from repro.configs.base import ShapeConfig
    from repro.optim import OptConfig
    from repro.train import LoopConfig, TrainSettings, train

    mesh = _mk((1, 1), ("data", "model"))
    rules = ShardRules.for_mesh(mesh)
    cfg = get_smoke_config("smollm-360m")
    obs = Observer(tracer=Tracer(), name="train")
    res = train(cfg, ShapeConfig("t", "train", 16, 8), mesh, rules,
                OptConfig(kind="adam", lr=1e-2), TrainSettings(),
                LoopConfig(steps=2, ckpt_every=0, log_every=0), obs=obs)
    snap = res["metrics"]
    assert snap["step_ms"]["count"] == 2
    assert snap["step_ms"]["p50"] > 0
    info = validate(obs.tracer.events)
    names = [e["name"] for e in obs.tracer.events if e["ph"] == "B"]
    # four phase spans per step, every one balanced
    assert info["spans"] == 8
    for phase in ("stage_batch", "h2d", "dispatch", "device_wait"):
        assert names.count(phase) == 2
