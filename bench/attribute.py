"""Attribute a cell's device time and idle time to the program's own
named scopes and spans, from one traced run on the chip.

    python bench/attribute.py --workload <name> --seed <n> [--out <dir>]

- A training cell: the compiled step's HLO text maps each operation to a
  part of the step (bench/scopes.py); the cell's first ``check_steps``
  steps run, then ``trace_steps`` steps are traced.  Prints each part's
  device self time per step beside the traced busy time per step, the
  largest operations of each part, and the cell's per-layer metrics.
- A serving cell: the cell's traced run (bench/kinds/serve.py) with its
  profile kept.  Every idle gap of the first chip in the traced window is
  labelled ``<bench.* annotation>/<innermost repro.* span>`` (the
  program's spans, mirrored into the profiler by obs/trace.py), or by the
  benchmark annotation alone where no program span is open.  Prints the
  longest gaps (each with where it starts in its innermost program span,
  and that span's length), the idle time under each label, and the
  cell's per-layer metrics.

The summary is the last line of standard output; with ``--out`` it is
also written to ``<out>/attribute-<workload>-<seed>.json``.  Without a
TPU it exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, scopes, trace_reduce  # noqa: E402

PROGRAM = "repro."


def host_annotations(pd, prefix: str) -> list[tuple[int, int, str]]:
    """(start, end, name) of the host plane's annotations named
    ``prefix``*."""
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(prefix)]


def idle_gaps(pd, t0: int, t1: int) -> list[tuple[int, int]]:
    """The first chip's idle intervals inside [t0, t1]."""
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [(max(e.start_ns, t0), min(e.start_ns + e.duration_ns, t1))
                   for line in plane.lines if line.name == "XLA Ops"
                   for e in line.events]
            edges = [t0] + [x for m in trace_reduce._union(
                (s, e) for s, e in ops if e > s) for x in m] + [t1]
            return [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    raise ValueError("trace holds no device plane with XLA Ops")


def innermost(spans, t: float):
    """The span open at ``t`` that began last (of two that began
    together, the one that ends first), or None."""
    open_ = [h for h in spans if h[0] <= t < h[1]]
    return max(open_, key=lambda h: (h[0], -h[1])) if open_ else None


def label(bench, program, s: int, e: int) -> str:
    """``<bench label>/<innermost program span>`` open at the middle of
    [s, e]; the bench label alone where no program span is open."""
    mid = (s + e) / 2
    outer, inner = innermost(bench, mid), innermost(program, mid)
    outer = "(no annotation)" if outer is None else outer[2]
    return outer if inner is None else f"{outer}/{inner[2][len(PROGRAM):]}"


def labelled_gaps(pd, top: int = 10) -> dict:
    """The longest idle gaps of the traced window, each labelled, and the
    idle seconds under each label."""
    bench = host_annotations(pd, trace_reduce.ANNOTATION)
    program = host_annotations(pd, PROGRAM)
    if not bench:
        raise ValueError(f"trace holds no {trace_reduce.ANNOTATION}* annotation")
    t0, t1 = min(h[0] for h in bench), max(h[1] for h in bench)
    gaps = idle_gaps(pd, t0, t1)
    by_label = collections.Counter()
    for s, e in gaps:
        by_label[label(bench, program, s, e)] += (e - s) / 1e9
    longest = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        span = innermost(program, (s + e) / 2)
        where = [] if span is None else [(s - span[0]) / 1e9,
                                         (span[1] - span[0]) / 1e9]
        longest.append([label(bench, program, s, e), (e - s) / 1e9, *where])
    return {"window_s": (t1 - t0) / 1e9, "gaps": len(gaps), "longest": longest,
            "idle_s_by_label": dict(by_label.most_common())}


def train(cell: harness.Cell, seed: int, devices, scratch: Path) -> dict:
    import jax

    from bench import flops
    from bench.kinds import train as kind
    from bench.run import per_layer_metrics

    prog = kind.Program(cell, devices)
    smap = scopes.scope_map(prog.step.as_text())
    annotate = jax.profiler.TraceAnnotation
    steps0 = int(cell.traffic["check_steps"])
    n = int(cell.traffic["trace_steps"])
    params, state, _ = prog.checked_start(seed, steps0, annotate)
    jax.block_until_ready((params, state))
    with trace_reduce.profile(scratch / "trace"):
        t0 = time.perf_counter()
        for step in range(steps0, steps0 + n):
            with annotate("bench.feed"):
                batch = prog.feed(seed, step)
            with annotate("bench.dispatch"):
                params, state, metrics = prog.step(params, state, batch)
        with annotate("bench.wait"):
            jax.block_until_ready((params, state, metrics))
        window_s = time.perf_counter() - t0
    del params, state, metrics, batch, prog.step
    summary = trace_reduce.reduce_dir(scratch / "trace")
    parts = scopes.seconds_by_part(summary, smap)
    top = collections.defaultdict(list)
    for k, v in sorted(summary["ops"].items(), key=lambda kv: -kv[1]["s"]):
        part = smap.get(scopes.instruction(k), ("unscoped",))[0]
        if len(top[part]) < 5:
            top[part].append([k, 1e3 * v["s"] / n, v["text"][:160]])
    device = harness.device_record(devices)
    record = {"kind": "train", "chips": prog.chips, "steps": n,
              "window_s": window_s, "tokens": n * prog.rows * prog.seq,
              "flops_per_token": flops.train_flops_per_token(
                  prog.n_params, attn_layers=prog.cfg.n_layers,
                  heads=prog.cfg.n_heads, head_dim=prog.cfg.head_dim,
                  seq=prog.seq),
              "flat_len": prog.flat_len, "device_kind": device["kind"],
              "trace": summary}
    busy_ms = 1e3 * summary["busy_s"] / n
    total = sum(parts[p] for p in scopes.PARTS)
    return {
        "steps": n,
        "busy_ms_per_step": busy_ms,
        "ms_per_step": {k: 1e3 * v / n for k, v in parts.items()},
        "sum_over_busy": 1e3 * total / n / busy_ms,
        "top_ops_ms_per_step": top,
        "metrics": {k: v for k, (v, _) in per_layer_metrics(cell, record).items()},
        "device": device,
    }


def serve(cell: harness.Cell, seed: int, seconds: float, devices,
          scratch: Path) -> dict:
    from jax.profiler import ProfileData

    from bench.kinds import serve as kind
    from bench.run import per_layer_metrics

    out = kind.run(cell, seed=seed, seconds=seconds, trace=True,
                   t_start=T_START, devices=devices, scratch=scratch)
    pd = ProfileData.from_file(str(trace_reduce.find_xplane(scratch / "trace")))
    spans = out["record"]["spans"]
    return {
        "idle": labelled_gaps(pd),
        "span_counts": {k: len(v) for k, v in spans.items()},
        "span_mean_ms": {k: 1e3 * sum(v) / len(v) for k, v in spans.items()},
        "metrics": {k: v for k, (v, _) in
                    per_layer_metrics(cell, out["record"]).items()},
        "correct": all(c.ok for c in out["checks"]) and out["failed"] == 0,
        "device": out["device"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", help="a directory to write the summary to")
    args = ap.parse_args(argv)
    try:
        cell = harness.load_cell(args.workload)
        devices = harness.check_devices(cell.chips)
    except (harness.SetupError, ImportError) as e:
        print(f"attribute: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    scratch = ROOT / ".bench_scratch" / f"attribute-{cell.name}"
    shutil.rmtree(scratch, ignore_errors=True)
    scratch.mkdir(parents=True)
    if cell.traffic["kind"] == "train":
        result = train(cell, args.seed, devices, scratch)
    else:
        seconds = harness.read_json(ROOT / "BENCHMARK.json")["run_seconds"]
        result = serve(cell, args.seed, float(seconds), devices, scratch)
    result = {"workload": cell.name, "seed": args.seed, **result}
    if args.out:
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        (out / f"attribute-{cell.name}-{args.seed}.json").write_text(
            json.dumps(result))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
