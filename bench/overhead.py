"""What arming the program's tracer costs a serving cell, with the
profiler off: the cell's traffic driven as bench/kinds/serve.py drives it,
once with no tracer and once with an ``Observer`` holding a ``Tracer``
(every span also opens a profiler annotation, obs/trace.py), for each
seed.  Both halves of a seed take the same schedule and weights.

    python bench/overhead.py --workload <serving cell> --seeds <n> [<n> ...]

Prints one JSON line per run (``traced`` 0 or 1) with the cell's
end-to-end numbers, then a summary line of the medians.  Without a TPU it
exits 2.
"""
from __future__ import annotations

import argparse
import gc
import json
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import generators, harness, stats  # noqa: E402


def one_run(cell: harness.Cell, seed: int, seconds: float, devices,
            traced: bool) -> dict:
    from bench.kinds import serve as kind

    tr = cell.traffic
    server = kind.Server(cell, devices, traced=traced)
    router = server.start(seed)
    kind.warm_shapes(router, server.cfg.vocab)
    reqs = generators.serve_requests(seed, tr, server.cfg.vocab, seconds)
    base, due_abs = kind.drive(router, reqs, seconds,
                               drain_s=float(tr["drain_s"]))
    ttft, itl, tokens, failed = kind.latencies(router, reqs, base, seconds,
                                               due_abs)
    events = len(server.obs.tracer.events) if traced else 0
    del router, server
    gc.collect()
    return {"seed": seed, "traced": int(traced), "failed": failed,
            "events": events,
            "ttft_p95_ms": stats.percentile(ttft, 95),
            "itl_p95_ms": float(stats.percentile(itl, 95)),
            "serve_tokens_per_s": tokens / seconds}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    try:
        cell = harness.load_cell(args.workload)
        devices = harness.check_devices(cell.chips)
    except (harness.SetupError, ImportError) as e:
        print(f"overhead: {e}", file=sys.stderr)
        return 2
    if cell.traffic["kind"] != "serve":
        print(f"overhead: {cell.name} is not a serving cell", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    seconds = float(harness.read_json(ROOT / "BENCHMARK.json")["run_seconds"])
    runs = []
    for i, seed in enumerate(args.seeds):
        # alternate which half runs first
        for traced in ((False, True) if i % 2 == 0 else (True, False)):
            runs.append(one_run(cell, seed, seconds, devices, traced))
            print(json.dumps(runs[-1]), flush=True)
    summary = {}
    for key in ("ttft_p95_ms", "itl_p95_ms", "serve_tokens_per_s"):
        off = statistics.median(r[key] for r in runs if not r["traced"])
        on = statistics.median(r[key] for r in runs if r["traced"])
        summary[key] = {"off": off, "traced": on, "ratio": on / off}
    print(json.dumps({"workload": cell.name, "medians": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
