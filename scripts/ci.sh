#!/usr/bin/env bash
# Tier-1 CI: test suite on a forced 8-device host mesh + the overlap and
# serve benchmarks in smoke mode (write BENCH_overlap.json /
# BENCH_serve.json to the repo root).  The serve bench gates on its
# dispatch counters: steady-state decode must show ZERO new executable
# builds after warmup (the AOT cache must not silently start missing).
#
#   scripts/ci.sh             # full run
#   scripts/ci.sh -k buckets  # extra args forwarded to pytest
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== repo hygiene =="
if git ls-files | grep -q '\.pyc$'; then
  echo "FAIL: compiled bytecode is tracked in git:" >&2
  git ls-files | grep '\.pyc$' >&2
  exit 1
fi
echo "  no tracked *.pyc"

echo "== tier-1 suite (8 forced host devices; 200-episode engine fuzz) =="
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  ENGINE_FUZZ_EPISODES="${ENGINE_FUZZ_EPISODES:-200}" \
  CHAOS_FUZZ_EPISODES="${CHAOS_FUZZ_EPISODES:-6}" \
  ROUTER_FUZZ_EPISODES="${ROUTER_FUZZ_EPISODES:-6}" \
  python -m pytest -x -q "$@"

echo "== overlap bench (smoke) =="
python benchmarks/overlap_bench.py --smoke --json BENCH_overlap.json >/dev/null
python - <<'EOF'
import json
rep = json.load(open("BENCH_overlap.json"))
s = rep["step_ms"]
for k in ("monolithic_flat", "bucketed_flat", "zero_flat", "legacy_gspmd"):
    if k in s:
        print(f"  {k:16s} {s[k]['step_ms']:8.2f} ms/step  buckets={s[k]['num_buckets']}")
d = rep["dispatch"]
print(f"  dispatch: cold {d['cold_ms']:.1f} ms, cached {d['cached_us']:.0f} us, "
      f"presharded {d['presharded_us']:.0f} us")
EOF

echo "== serve bench (smoke, 8 forced host devices) =="
python benchmarks/serve_bench.py --smoke --json BENCH_serve.json >/dev/null
python - <<'EOF'
import json, sys
rep = json.load(open("BENCH_serve.json"))
for name, row in rep["modes"].items():
    kv = ""
    if "kv_reserved_bytes" in row:
        kv = (f"  kv {row['kv_peak_used_bytes'] / 2**20:5.1f}"
              f"/{row['kv_reserved_bytes'] / 2**20:5.1f} MiB used/reserved")
    lat = ""
    if "p50_ms_per_token" in row:
        lat = (f"  p50 {row['p50_ms_per_token']:7.1f} ms/tok  "
               f"p99 {row['p99_ms_per_token']:7.1f} ms/tok")
    print(f"  {name:24s} {row['tokens_per_s']:7.1f} tok/s{lat}{kv}")
h = rep["headline"]
print(f"  speedup_vs_static {h['speedup_vs_static']:.2f}x  "
      f"p99_ratio {h['p99_ratio_vs_static']:.2f}  "
      f"steady_builds_delta {h['steady_builds_delta']}  "
      f"all_builds_delta {h['all_steady_builds_delta']}  "
      f"kv_ratio {h['kv_reserved_ratio_paged_vs_slotted']:.2f}")
print(f"  paged_parity {h['paged_greedy_parity']}  "
      f"prefix_parity {h['prefix_greedy_parity']}  "
      f"preempt_parity {h['preempt_greedy_parity']}  "
      f"prefix_hit_rate {h['prefix_cache_hit_rate']:.2f}  "
      f"prefill_ratio {h['prefix_prefill_token_ratio']:.2f}  "
      f"preemptions {h['preemptions_timed']}+{h['parity_check_preemptions']}")
print(f"  recurrent_parity {h['recurrent_greedy_parity']}  "
      f"recurrent_preempt_parity {h['recurrent_preempt_parity']} "
      f"(x{h['recurrent_preemptions']})  "
      f"hybrid_parity {h['hybrid_greedy_parity']}  "
      f"recurrent_builds_delta {h['recurrent_steady_builds_delta']}")
td = rep["modes"]["continuous_tiered"]
print(f"  tiered: restores {h['tiered_restores']}  "
      f"replayed {h['tiered_replayed_tokens']}  "
      f"o_copy {h['tiered_o_copy_resume']}  "
      f"parity {h['tiered_token_parity']}  "
      f"spilled {td['spilled_bytes'] / 2**20:.1f} MiB  "
      f"builds_delta {h['tiered_steady_builds_delta']}")
sp = rep["modes"]["continuous_spec"]
print(f"  spec: parity {h['spec_greedy_parity']}  "
      f"accept_rate {h['spec_acceptance_rate']:.2f}  "
      f"tok/lane-round {h['spec_tokens_per_decode_dispatch']:.2f}  "
      f"accepted {sp['spec_accepted']}  rejected {sp['spec_rejected']}  "
      f"builds_delta {h['spec_steady_builds_delta']}")
print(f"  chaos: faults {h['chaos_faults_fired']}  all_ok {h['chaos_all_ok']}  "
      f"parity {h['chaos_token_parity']}  "
      f"overhead {h['chaos_recovery_overhead']:.2f}x  "
      f"builds_delta {h['chaos_steady_builds_delta']}")
print(f"  router: lost {h['router_requests_lost']}  all_ok {h['router_all_ok']}  "
      f"failover_parity {h['router_failover_parity']}  "
      f"failovers {h['router_failovers']}  migrated {h['router_migrated']}  "
      f"builds_delta {h['router_steady_builds_delta']}")
tr = rep["modes"]["continuous_traced"]
print(f"  traced: overhead {h['traced_overhead_ratio']:.2f}x  "
      f"parity {h['traced_token_parity']}  "
      f"events {tr['trace_events']}  spans {tr['trace_spans']}  "
      f"builds_delta {h['traced_steady_builds_delta']}")
print(f"  slowest AOT builds: " + ", ".join(
      f"{s:.2f}s" for _, s in rep["meta"]["slowest_builds"][:3]) +
      f"  (total {rep['meta']['aot_build_s_total']:.1f}s)")
if h["steady_builds_delta"] != 0:
    sys.exit("FAIL: serve decode built executables after warmup "
             "(AOT dispatch cache regression)")
if h["all_steady_builds_delta"] != 0:
    sys.exit("FAIL: an engine mode built executables after warmup — "
             "prefix/preempt scheduling must dispatch purely from the "
             "prebuilt AOT cache")
if not h["paged_greedy_parity"]:
    sys.exit("FAIL: paged engine diverged from the slotted engine under "
             "greedy decoding")
if not h["prefix_greedy_parity"]:
    sys.exit("FAIL: prefix-cached engine diverged from the slotted engine "
             "under greedy decoding")
if not h["preempt_greedy_parity"]:
    sys.exit("FAIL: preempting engine diverged from the slotted engine "
             "under greedy decoding")
if h["prefix_cache_hit_rate"] <= 0:
    sys.exit("FAIL: shared-prefix workload produced no prefix-cache hits")
if h["prefix_prefill_token_ratio"] >= 0.6:
    sys.exit("FAIL: prefix caching computed >= 0.6x the no-cache prefill "
             "tokens on the shared-prefix workload")
if h["preemptions_timed"] + h["parity_check_preemptions"] <= 0:
    sys.exit("FAIL: the preempt mode never preempted — its parity gate is "
             "vacuous (pool sizing no longer squeezes the lanes)")
paged = rep["modes"]["continuous_paged"]
slotted = rep["modes"]["continuous_fused"]
if paged["kv_reserved_bytes"] >= slotted["kv_reserved_bytes"]:
    sys.exit("FAIL: paged layout did not reserve less KV HBM than the "
             "slotted max_slots*max_len layout")
if not h["recurrent_greedy_parity"]:
    sys.exit("FAIL: the recurrent (ssm/xlstm) slot engine diverged from "
             "generate_static under greedy decoding")
if not h["hybrid_greedy_parity"]:
    sys.exit("FAIL: the hybrid (zamba) slot engine diverged from "
             "generate_static under greedy decoding")
if not h["recurrent_preempt_parity"] or h["recurrent_preemptions"] <= 0:
    sys.exit("FAIL: recurrent preempt-and-requeue resume is not "
             "token-for-token (or the parity drive never preempted)")
if h["recurrent_steady_builds_delta"] != 0:
    sys.exit("FAIL: a recurrent/hybrid engine mode built executables "
             "after warmup (AOT dispatch cache regression)")
if h["tiered_restores"] <= 0:
    sys.exit("FAIL: the tiered mode never restored from the host tier — "
             "its O(copy) gate is vacuous (pool sizing no longer forces "
             "preemptions, or spills are being dropped)")
if not h["tiered_token_parity"]:
    sys.exit("FAIL: host-tier spill/restore changed greedy tokens — "
             "restored lanes must continue bitwise-identically")
if not h["tiered_o_copy_resume"]:
    sys.exit("FAIL: a tier-restored lane replayed decode steps or "
             "re-prefilled its prompt — resume must be O(bytes copied), "
             "not O(generated tokens)")
if h["tiered_steady_builds_delta"] != 0:
    sys.exit("FAIL: the tiered mode built executables after prebuild — "
             "spill/restore transport must ride the AOT cache")
if not h["spec_greedy_parity"]:
    sys.exit("FAIL: speculative decoding changed greedy tokens — the "
             "draft/verify commit rule must be bitwise vs the sequential "
             "engine")
if h["spec_acceptance_rate"] <= 0:
    sys.exit("FAIL: the spec mode accepted no draft tokens — its parity "
             "and speedup gates are vacuous (draft too far from target?)")
if rep["modes"]["continuous_spec"]["spec_rejected"] <= 0:
    sys.exit("FAIL: the spec mode rejected no draft tokens — the "
             "rollback path was never exercised (draft == target?)")
if h["spec_tokens_per_decode_dispatch"] <= 1.0:
    sys.exit("FAIL: spec decode committed <= 1 token per lane-round — "
             "speculation is not paying for its verify dispatches")
if h["spec_steady_builds_delta"] != 0:
    sys.exit("FAIL: the spec mode built executables after prebuild — "
             "draft prefill + verify must ride the AOT cache")
if h["chaos_faults_fired"] <= 0:
    sys.exit("FAIL: the chaos mode injected no faults — its recovery "
             "gates are vacuous (FaultPlan rates/seed no longer fire)")
if not h["chaos_all_ok"]:
    sys.exit("FAIL: a fault-injected request did not recover to status "
             "'ok' (retry/quarantine path regression)")
if not h["chaos_token_parity"]:
    sys.exit("FAIL: fault recovery changed greedy tokens — preempt-and-"
             "replay resume is no longer bitwise")
if h["chaos_steady_builds_delta"] != 0:
    sys.exit("FAIL: fault recovery built new executables — retries must "
             "reuse the prebuilt bucketed programs")
if h["router_requests_lost"] != 0:
    sys.exit("FAIL: the router lost requests across a replica crash — "
             "failover must conserve every submitted request")
if not h["router_all_ok"]:
    sys.exit("FAIL: a request did not finish 'ok' after replica "
             "crash/drain (router failover regression)")
if not h["router_failover_parity"]:
    sys.exit("FAIL: failover changed greedy tokens — the rebuilt resume "
             "on a survivor is no longer bitwise")
if h["router_failovers"] <= 0:
    sys.exit("FAIL: the router mode never failed over — its parity gate "
             "is vacuous (the kill tick no longer strands requests)")
if h["router_steady_builds_delta"] != 0:
    sys.exit("FAIL: the replica fleet built executables after prebuild — "
             "replicas must share one AOT cache")
if not h["traced_token_parity"]:
    sys.exit("FAIL: arming the observer changed greedy tokens — tracing "
             "must be a pure host-side observer")
if not tr["decode_steps_match"]:
    sys.exit("FAIL: the traced drive took a different number of decode "
             "steps than the untraced drive — tracing perturbed "
             "scheduling")
if h["traced_steady_builds_delta"] != 0:
    sys.exit("FAIL: the traced drive built executables after prebuild — "
             "observability must not change executable keys")
if "metrics" not in tr or tr["metrics"].get("decode_steps", {}).get("value", 0) <= 0:
    sys.exit("FAIL: the traced mode's embedded metrics snapshot is "
             "missing or has no decode_steps counter")
EOF

echo "== trace artifact check =="
python - <<'EOF'
import json, sys
sys.path.insert(0, "src")
from repro.obs import load_jsonl, validate
ev = load_jsonl("BENCH_serve_trace.jsonl")
info = validate(ev)   # spans balance, timelines terminal-complete
chrome = json.load(open("BENCH_serve_trace.json"))
if not chrome.get("traceEvents"):
    sys.exit("FAIL: BENCH_serve_trace.json has no traceEvents")
print(f"  {info['events']} events / {info['spans']} spans / "
      f"{info['requests']} requests / {info['terminals']} terminals; "
      f"chrome trace {len(chrome['traceEvents'])} entries")
EOF

echo "== docs link check =="
python - <<'EOF'
import os, re, sys
paths = ["README.md"] + sorted(
    os.path.join("docs", f) for f in os.listdir("docs") if f.endswith(".md"))
bad = []
for path in paths:
    base = os.path.dirname(path)
    text = open(path).read()
    for m in re.finditer(r"\[[^\]]*\]\(([^)\s#]+)(#[^)]*)?\)", text):
        target = m.group(1)
        if target.startswith(("http://", "https://", "mailto:")):
            continue
        if not os.path.exists(os.path.normpath(os.path.join(base, target))):
            bad.append(f"{path}: {target}")
if bad:
    sys.exit("FAIL: broken relative links:\n  " + "\n  ".join(bad))
print(f"  {len(paths)} files, all relative links resolve")
EOF
echo "CI OK — BENCH_overlap.json + BENCH_serve.json written"
