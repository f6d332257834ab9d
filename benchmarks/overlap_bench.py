"""Overlap + dispatch benchmark: bucketed flat-gradient engine vs the
monolithic flat all-reduce, and SynkFunction dispatch overhead cold vs
cached.  Emits machine-readable JSON so the perf trajectory is tracked
PR-over-PR.

Runs on a forced 8-device host mesh (the env var must be set before jax
initializes, so run this module as a script — ``benchmarks/run.py`` spawns
it as a subprocess).

    python benchmarks/overlap_bench.py --smoke --json BENCH_overlap.json

JSON schema (all times are medians over --iters):
    meta:       devices / backend / jax version / config / smoke flag
    step_ms:    per-train-step wall time for each engine configuration
                (monolithic flat, bucketed flat, zero flat, legacy gspmd)
                + the bucket counts that produced them
    dispatch:   SynkFunction overhead — cold_ms (build+compile+run),
                cached_us (steady-state per call), presharded_us (per call
                when device_put is skippable), and the function's counters
"""
from __future__ import annotations

import os

# append (not setdefault): a pre-existing XLA_FLAGS (e.g. --xla_dump_to)
# must not suppress the forced host device count
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
# the forced host devices exist only on the CPU backend: never take a chip
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse     # noqa: E402
import json         # noqa: E402
import statistics   # noqa: E402
import sys          # noqa: E402
import time         # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax          # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.cache import enable_compile_cache  # noqa: E402


def _median_ms(fn, iters: int, warmup: int = 2) -> float:
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(ts)


# ---------------------------------------------------------------------------
# Train-step: monolithic vs bucketed vs zero vs legacy
# ---------------------------------------------------------------------------


def bench_step(smoke: bool, iters: int) -> dict:
    from repro.configs import get_smoke_config
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import _mk
    from repro.models.common import ShardRules
    from repro.optim import OptConfig
    from repro.train.loop import init_sharded
    from repro.train.step import TrainSettings, jit_train_step

    cfg = get_smoke_config("smollm-360m")
    B, S = (16, 8) if smoke else (64, 32)
    mesh = _mk((jax.device_count(), 1), ("data", "model"))
    shape = ShapeConfig("bench", "train", S, B)   # (seq_len, global_batch)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab, size=(B, S + 1)).astype(np.int32)

    # bucket_mb chosen so "bucketed" yields several buckets on the smoke
    # model while "monolithic" is guaranteed one bucket
    variants = {
        "monolithic_flat": (TrainSettings(faithful=True),
                            OptConfig(kind="adam", lr=1e-3, bucket_mb=1 << 12)),
        "bucketed_flat": (TrainSettings(faithful=True),
                          OptConfig(kind="adam", lr=1e-3, bucket_mb=0.05)),
        "zero_flat": (TrainSettings(flat_engine="zero"),
                      OptConfig(kind="adam", lr=1e-3, bucket_mb=0.05)),
        "legacy_gspmd": (TrainSettings(faithful=True, flat_engine="off"),
                         OptConfig(kind="adam", lr=1e-3)),
    }
    out: dict = {"global_batch": B, "seq_len": S, "config": "smollm-360m/smoke"}
    for name, (settings, opt) in variants.items():
        rules = ShardRules.for_mesh(mesh, faithful=settings.faithful)
        stepf, _, in_sh = jit_train_step(
            cfg, mesh, rules, opt, shape, settings, donate=False)
        params, opt_state = init_sharded(cfg, mesh, rules, opt, 0, settings)
        batch = {"tokens": jax.device_put(tokens, in_sh[2]["tokens"])}
        state = {"p": params, "o": opt_state}

        def one_step():
            state["p"], state["o"], m = stepf(state["p"], state["o"], batch)
            jax.block_until_ready(m["loss"])

        t0 = time.perf_counter()
        one_step()  # includes compile
        compile_ms = (time.perf_counter() - t0) * 1e3
        out[name] = {
            "step_ms": _median_ms(one_step, iters),
            "first_call_ms": compile_ms,
            "engine": stepf._flat_engine,
            "num_buckets": (stepf._flat_buckets.num_buckets
                            if stepf._flat_buckets else None),
        }
    return out


# ---------------------------------------------------------------------------
# Dispatch: SynkFunction per-call overhead, cold vs cached
# ---------------------------------------------------------------------------


def bench_dispatch(smoke: bool, iters: int) -> dict:
    import repro.core as synk

    ctx = synk.fork()
    n = ctx.n_data
    rows = 8 * n if smoke else 128 * n
    rng = np.random.default_rng(1)
    x = rng.normal(size=(rows, 64)).astype(np.float32)
    w = rng.normal(size=(64,)).astype(np.float32)

    f = synk.function(lambda x, w: jnp.mean(x @ w),
                      [synk.Scatter(), synk.Broadcast()], synk.Reduce("mean"))

    t0 = time.perf_counter()
    jax.block_until_ready(f(x, w))          # build + AOT compile + run
    cold_ms = (time.perf_counter() - t0) * 1e3

    k = max(iters * 10, 50)

    def cached():
        jax.block_until_ready(f(x, w))

    cached_ms = _median_ms(cached, k)

    xs = jax.device_put(x, ctx.sharding(ctx.data_spec(None)))
    ws = jax.device_put(w, ctx.sharding(jax.sharding.PartitionSpec()))

    def presharded():
        jax.block_until_ready(f(xs, ws))

    presharded_ms = _median_ms(presharded, k)

    return {
        "cold_ms": cold_ms,
        "cached_us": cached_ms * 1e3,
        "presharded_us": presharded_ms * 1e3,
        "rows": rows,
        "stats": dict(f.stats),
    }


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes / few iters (CI mode)")
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--json", default=None, help="also write JSON to this path")
    args = ap.parse_args(argv)
    enable_compile_cache()
    iters = args.iters or (3 if args.smoke else 10)

    report = {
        "meta": {
            "bench": "overlap",
            "devices": jax.device_count(),
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "smoke": bool(args.smoke),
            "iters": iters,
        },
        "step_ms": bench_step(args.smoke, iters),
        "dispatch": bench_dispatch(args.smoke, iters),
    }
    text = json.dumps(report, indent=2)
    print(text)
    if args.json:
        with open(args.json, "w") as fh:
            fh.write(text + "\n")
    return report


if __name__ == "__main__":
    main()
