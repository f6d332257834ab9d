"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  fig23_scaling    — paper Fig. 2/3 (DP speedup, fixed + scaled batch)
  table1_profile   — paper Table 1 (loop decomposition w/ blocking)
  roofline_report  — §Roofline terms per dry-run cell (this repo's tables)
  kernel_bench     — Pallas kernel micro-benchmarks
  overlap          — bucketed flat-gradient engine + dispatch overhead
                     (forced 8-device host mesh; also writes
                     BENCH_overlap.json to the repo root)
  serve            — continuous-batching serve engine vs the static-batch
                     loop on a Poisson arrival trace (8-device host mesh;
                     writes BENCH_serve.json to the repo root)

This process never imports JAX: a chip belongs to one process at a time,
so every phase runs as a child process, one after another.  The phases
that fake host devices pin themselves to the CPU backend.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def emit(name: str, us: float, derived: str = "") -> None:
    print(f"{name},{us:.1f},{derived}", flush=True)


def child(script: str, *args: str, timeout: int = 1800):
    """Run ``benchmarks/<script>`` as a child process with ``src`` on its
    path; returns the CompletedProcess (stdout/stderr captured)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", script), *args],
        capture_output=True, text=True, env=env, timeout=timeout)
    if r.returncode != 0:
        print(r.stdout[-2000:], file=sys.stderr)
        print(r.stderr[-2000:], file=sys.stderr)
    return r


def run_csv(script: str) -> bool:
    """A phase whose ``__main__`` prints CSV rows: pass them through."""
    r = child(script)
    print(r.stdout, end="", flush=True)
    return r.returncode == 0


def run_overlap(emit, smoke: bool = True,
                out_json: str | None = None) -> bool:
    """Run overlap_bench and surface headline numbers as CSV rows."""
    out_json = out_json or os.path.join(REPO, "BENCH_overlap.json")
    r = child("overlap_bench.py", "--json", out_json,
              *(["--smoke"] if smoke else []))
    if r.returncode != 0:
        return False
    with open(out_json) as fh:
        rep = json.load(fh)
    for name in ("monolithic_flat", "bucketed_flat", "zero_flat", "legacy_gspmd"):
        row = rep["step_ms"].get(name)
        if row:
            emit(f"overlap/{name}", row["step_ms"] * 1e3,
                 f"buckets={row['num_buckets']}")
    d = rep["dispatch"]
    emit("overlap/dispatch_cold", d["cold_ms"] * 1e3, "build+compile")
    emit("overlap/dispatch_cached", d["cached_us"], "steady-state")
    emit("overlap/dispatch_presharded", d["presharded_us"], "device_put skipped")
    return True


def run_serve(emit, smoke: bool = True, out_json: str | None = None) -> bool:
    """Run serve_bench and surface the headline rows as CSV."""
    out_json = out_json or os.path.join(REPO, "BENCH_serve.json")
    r = child("serve_bench.py", "--json", out_json,
              *(["--smoke"] if smoke else []))
    if r.returncode != 0:
        return False
    with open(out_json) as fh:
        rep = json.load(fh)
    for name, row in rep["modes"].items():
        emit(f"serve/{name}", 1e6 / row["tokens_per_s"],
             f"p99={row['p99_ms_per_token']:.0f}ms/tok")
    h = rep["headline"]
    emit("serve/speedup_vs_static", h["speedup_vs_static"] * 100,
         "continuous/static tokens-per-s x100")
    emit("serve/kv_reserved_ratio_paged",
         h["kv_reserved_ratio_paged_vs_slotted"] * 100,
         "paged/slotted KV reservation x100")
    # full acceptance: >= 2x tokens/s at equal-or-better p99 per-token
    # latency, zero executable builds after warmup on every engine mode,
    # paged greedy parity, and a real paged reservation saving
    ok = (h["speedup_vs_static"] >= 2.0
          and h["p99_ratio_vs_static"] <= 1.0
          and h["steady_builds_delta"] == 0
          and h["paged_steady_builds_delta"] == 0
          and h["paged_greedy_parity"]
          and h["kv_reserved_ratio_paged_vs_slotted"] < 1.0)
    if not ok:
        print(f"serve bench FAILED acceptance: {h}", file=sys.stderr)
    return ok


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig23,table1,roofline,kernels,overlap,serve")
    ap.add_argument("--full-overlap", action="store_true",
                    help="overlap bench at full (non-smoke) sizes")
    args = ap.parse_args()
    want = set(
        (args.only or "fig23,table1,roofline,kernels,overlap,serve").split(","))

    print("name,us_per_call,derived")
    phases = [
        ("overlap", lambda: run_overlap(emit, smoke=not args.full_overlap)),
        ("serve", lambda: run_serve(emit, smoke=not args.full_overlap)),
        ("roofline", lambda: run_csv("roofline_report.py")),
        ("kernels", lambda: run_csv("kernel_bench.py")),
        ("table1", lambda: run_csv("table1_profile.py")),
        ("fig23", lambda: run_csv("fig23_scaling.py")),
    ]
    ok = True
    for name, run in phases:
        if name in want:
            ok = run() and ok
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
