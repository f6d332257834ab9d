"""Find the highest open-loop rate a serving cell's program sustains.

    python bench/sweep.py --workload <serve cell> --seed <n> --seconds <s> \
        --rates 2.0,2.5,3.0

One process, one set-up: for each rate, the cell's own traffic mix at that
rate (warm-up, window, drain), then what a rate needs to be judged by:
TTFT quartiles in the window's first and last thirds (a backlog that grows
makes the last third's wait grow), the requests not finished when the
drain ends, and tokens per second.  The cell's traffic file then takes
0.8 of the highest rate whose backlog did not grow.  Needs the chip.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import generators, harness, stats  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    devices = harness.check_devices(cell.chips)
    harness.enable_compile_cache()
    from bench.kinds import serve

    server = serve.Server(cell, devices)
    router = server.start(args.seed)
    serve.warm_shapes(router, server.cfg.vocab)
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = {**cell.traffic, "rate_per_s": rate}
        reqs = generators.serve_requests(args.seed, traffic, server.cfg.vocab,
                                         args.seconds)
        for r in reqs:          # request ids stay unique across rates
            r.index += int(rate * 1000) << 20
        t0 = time.perf_counter()
        base, due = serve.drive(router, reqs, args.seconds,
                                drain_s=float(traffic["drain_s"]))
        ttft, itl, tokens, failed = serve.latencies(router, reqs, base,
                                                    args.seconds, due)
        win = [r for r in reqs if r.in_window]
        third = len(win) // 3
        first, last = ttft[:third], ttft[-third:]
        print(json.dumps({
            "rate_per_s": rate, "requests": len(win), "failed": failed,
            "ttft_ms_first_third": [stats.percentile(first, q) for q in (25, 50, 75)],
            "ttft_ms_last_third": [stats.percentile(last, q) for q in (25, 50, 75)],
            "ttft_p95_ms": stats.percentile(ttft, 95),
            "itl_p50_ms": float(stats.percentile(itl, 50)),
            "itl_p95_ms": float(stats.percentile(itl, 95)),
            "serve_tokens_per_s": tokens / args.seconds,
            "queued_at_end": len(router.queue),
            "wall_s": time.perf_counter() - t0,
        }), flush=True)
        for rid in list(router.records):
            router.cancel(rid)
        router.run()
    return 0


if __name__ == "__main__":
    sys.exit(main())
