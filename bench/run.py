"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and per-layer metrics are found
by name from BENCHMARK.json (see bench/harness.py).  ``--trace 0`` prints
the cell's end-to-end metrics; ``--trace 1`` traces the window with the
JAX profiler and prints its per-layer metrics.  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result.  The numbers that decide ``correct`` are printed beside their
limits as the last lines of standard error and under ``checks``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

SCRATCH = ROOT / ".bench_scratch"


def per_layer_metrics(cell: harness.Cell, record: dict) -> dict:
    """Each per-layer metric's reader, found by name, over the traced run's
    record; a reader that finds nothing returns None and is left out."""
    out = {}
    for m in cell.per_layer:
        value = cell.metric_reader(m["name"]).read(record)
        if value is not None:
            out[m["name"]] = (float(value), m["unit"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = harness.load_cell(args.workload)
        devices = harness.check_devices(cell.chips)
    except (harness.SetupError, ImportError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    scratch = SCRATCH / cell.name
    shutil.rmtree(scratch, ignore_errors=True)
    scratch.mkdir(parents=True)
    try:
        out = cell.kind().run(cell, seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace), t_start=T_START,
                              devices=devices, scratch=scratch)
    except Exception:
        traceback.print_exc()
        return 1
    finally:
        shutil.rmtree(scratch, ignore_errors=True)

    if args.trace:
        metrics = per_layer_metrics(cell, out["record"])
    else:
        values = {"setup_s": out["setup_s"], **out["e2e"]}
        metrics = {m["name"]: (float(values[m["name"]]), m["unit"])
                   for m in cell.end_to_end}
    checks = out["checks"]
    line = harness.result_line(
        checks=checks, attempted=out["attempted"], failed=out["failed"],
        metrics=metrics, device=out["device"],
        breakdown=out.get("breakdown") if args.trace else None)
    print(json.dumps(line), flush=True)
    print(f"bench: {cell.name} seed {args.seed} attempted {out['attempted']} "
          f"failed {out['failed']} correct {line['correct']}",
          file=sys.stderr, flush=True)
    harness.print_checks(checks)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
