"""Split a serving cell's decode step and one prefill by the program's
named scopes, from device traces on the chip.

    python bench/serve_split.py --workload <serve cell> --seed <n> \
        [--steps 20] [--prefills 4] [--bucket 2048] [--out <dir>]

One process: the cell's server with weights from the seed and every
shape warmed.  Every lane is then admitted with a prompt of the traffic's
median length and decoded a few steps, and the engine's own compiled
decode program is called ``--steps`` more times under the profiler; then
its prefill program for ``--bucket`` is called ``--prefills`` times into
the lanes.  Each operation of a trace is mapped to the scope it runs
under by the compiled module's HLO text (bench/scopes.py): the innermost
of ``zamba.ssd`` (inside ``zamba.mamba``), ``attention`` (inside
``zamba.shared_block`` for Zamba2, or a decoder block), ``zamba.mamba``,
``zamba.shared_block``; ``other`` where the op_name names none of them,
``unscoped`` where there is no op_name.  Prints, per program, device ms
per call by part beside the traced busy time per call, and the largest
operations of each part; the summary is the last line of standard output
(and ``<out>/serve-split-<workload>-<seed>.json`` with ``--out``).  Needs
the chip.
"""
from __future__ import annotations

import argparse
import collections
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import generators, harness, scopes, trace_reduce  # noqa: E402

SCOPES = ("zamba.ssd", "attention", "zamba.mamba", "zamba.shared_block")


def part(op_name: str) -> str:
    if not op_name:
        return "unscoped"
    comps = op_name.split("/")
    return next((s for s in SCOPES if s in comps), "other")


def split(compiled, trace_dir, calls: int) -> dict:
    """Device ms per call of each part, over one traced program."""
    names = scopes.op_names(compiled.as_text())
    summary = trace_reduce.reduce_dir(trace_dir)
    ms = collections.Counter()
    top = collections.defaultdict(list)
    for key, op in summary["ops"].items():
        p = part(names.get(scopes.instruction(key), ""))
        ms[p] += op["s"] * 1e3 / calls
        top[p].append((op["s"] * 1e3 / calls, key))
    return {
        "calls": calls,
        "busy_ms_per_call": summary["busy_s"] * 1e3 / calls,
        "ms_per_call": dict(ms),
        "largest": {p: [[k, v] for v, k in sorted(t, reverse=True)[:5]]
                    for p, t in top.items()},
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--prefills", type=int, default=4)
    ap.add_argument("--bucket", type=int, default=2048)
    ap.add_argument("--out")
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    devices = harness.check_devices(cell.chips)
    harness.enable_compile_cache()
    import jax
    import numpy as np

    from bench.kinds import serve

    server = serve.Server(cell, devices)
    router = server.start(args.seed)
    serve.warm_shapes(router, server.cfg.vocab)
    eng = router.replicas[0].engine
    lanes, vocab = eng.econ.max_slots, server.cfg.vocab
    plen = int(cell.traffic["prompt"]["median"])
    rng = generators.rng(args.seed, 9)
    for i in range(lanes):
        router.submit(rng.integers(0, vocab, plen, dtype=np.int32),
                      max_new_tokens=4 * args.steps + 8, temperature=0.0,
                      rid=(1 << 41) + i)
    while any(s is None for s in eng.slots):
        router.step()
    for _ in range(4):
        router.step()

    out = {"workload": cell.name, "seed": args.seed, "lanes": lanes,
           "bucket": args.bucket}
    scratch = ROOT / ".bench_scratch" / "serve_split"
    shutil.rmtree(scratch, ignore_errors=True)
    put = lambda v, dt: jax.device_put(np.asarray(v, dt), eng._rep)

    decode = eng._decode_exe()
    state, tok = decode(eng.params, eng.state)          # one untraced call
    tok.block_until_ready()
    with trace_reduce.profile(scratch / "decode"):
        with jax.profiler.TraceAnnotation("bench.decode"):
            for _ in range(args.steps):
                state, tok = decode(eng.params, state)
            tok.block_until_ready()
    out["decode"] = split(decode, scratch / "decode", args.steps)

    prefill = eng._prefill_exe(args.bucket)
    n = min(args.bucket, eng.econ.max_len) - 1
    prompt = put(rng.integers(0, vocab, (1, args.bucket), dtype=np.int32), np.int32)
    args_of = lambda slot: (prompt, put(slot, np.int32), put(n, np.int32),
                            put(n + 8, np.int32), put(0.0, np.float32),
                            put(0, np.int32), put(1.0, np.float32))
    state, tok = prefill(eng.params, state, *args_of(0))
    tok.block_until_ready()
    with trace_reduce.profile(scratch / "prefill"):
        with jax.profiler.TraceAnnotation("bench.prefill"):
            for i in range(args.prefills):
                state, tok = prefill(eng.params, state, *args_of(i % lanes))
            tok.block_until_ready()
    out["prefill"] = split(prefill, scratch / "prefill", args.prefills)
    shutil.rmtree(scratch, ignore_errors=True)

    for prog in ("decode", "prefill"):
        r = out[prog]
        print(f"{prog}: busy {r['busy_ms_per_call']:.3f} ms a call; " + ", ".join(
            f"{p} {v:.3f}" for p, v in sorted(r["ms_per_call"].items())),
            file=sys.stderr)
    line = json.dumps(out)
    if args.out:
        path = Path(args.out) / f"serve-split-{cell.name}-{args.seed}.json"
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
