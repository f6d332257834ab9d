"""Readings that the limits of ``correct`` are set from.  Not run by the
benchmark's own runs; needs the chip.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--variants control,half,exchange] [--seconds 10]

Prints one JSON line per seed and reading:

- ``program``: the program against the float32 reference, exactly as a
  run compares it (training: the first steps of the compiled step; serving:
  a short window at the cell's own load and the same sample of requests).
- ``control``: the reference in fp8 in the program's place (training: the
  first steps; serving: at every position of the same prompts and served
  tokens, the gap of the token the fp8 reference puts first).
- ``half``: training, the reference with half of each batch left out and
  the mean taken over the rest.
- ``exchange``: training on several chips, the reference with the exchange
  between chips left out, as the first chip sees it: the mean over its own
  share of the rows.

Training readings need no window: one process builds the compiled step
once and reads every seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def train_readings(cell, devices, seeds, variants) -> None:
    import jax

    from bench.kinds import train

    prog = train.Program(cell, devices)
    shares = {"half": 0.5, "exchange": 1.0 / cell.chips}
    for seed in seeds:
        t0 = time.perf_counter()
        params, state, got = prog.checked_start(
            seed, int(cell.traffic["check_steps"]), jax.profiler.TraceAnnotation)
        del params, state
        want = train.reference_readings(cell, seed, devices=devices)
        emit(seed=seed, reading="program", **train.numbers(got, want),
             losses=got["losses"], ref_losses=want["losses"],
             seconds=time.perf_counter() - t0)
        for v in variants:
            t0 = time.perf_counter()
            alt = train.reference_readings(
                cell, seed, policy="fp8" if v == "control" else "f32",
                row_share=shares.get(v, 1.0), devices=devices)
            emit(seed=seed, reading=v, **train.numbers(alt, want),
                 seconds=time.perf_counter() - t0)


def serve_readings(cell, devices, seeds, variants, seconds) -> None:
    from bench import generators
    from bench.kinds import serve

    server = serve.Server(cell, devices)
    for seed in seeds:
        router = server.start(seed)
        serve.warm_shapes(router, server.cfg.vocab)
        reqs = generators.serve_requests(seed, cell.traffic, server.cfg.vocab,
                                         seconds)
        serve.drive(router, reqs, seconds, drain_s=float(cell.traffic["drain_s"]))
        sample = serve.check_sample(seed, reqs, router,
                                    int(cell.traffic["check_requests"]))
        del router
        gaps = serve.reference_gaps(cell, seed, sample)
        emit(seed=seed, reading="program", logit_gap=float(gaps.max()),
             tokens=int(gaps.size), positive=int((gaps > 0).sum()))
        if "control" in variants:
            ctl = serve.reference_gaps(cell, seed, sample, control=True)
            emit(seed=seed, reading="control", logit_gap=float(ctl.max()),
                 tokens=int(ctl.size), positive=int((ctl > 0).sum()))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    devices = harness.check_devices(cell.chips)
    harness.enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    variants = [v for v in args.variants.split(",") if v]
    if cell.traffic["kind"] == "train":
        train_readings(cell, devices, seeds, variants)
    else:
        serve_readings(cell, devices, seeds, variants, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
