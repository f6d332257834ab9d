#!/usr/bin/env python3
"""Chip smoke test: the trainer and the serve engine, once each, on a TPU at
smollm-360m's full published width (random weights from a seed).

    python chip_smoke.py               # one chip: train phase, then serve phase
    python chip_smoke.py --four-chips  # four chips: the data-parallel path only

One chip
  train  The faithful flat engine (paper Appendix A: bucketed all-reduce,
         then the fused Pallas Adam kernel) on a one-chip data mesh, a few
         steps on one seeded batch.  Checks: the step lowers to the
         faithful engine; its compiled program holds the Pallas kernel
         (``tpu_custom_call``) and the jnp-reference step's does not;
         losses are finite and fall; one step through the kernel and one
         through the reference give the same parameters.
  serve  ``Router``/``ServeEngine`` configured as ``launch/serve.py`` does;
         8 seeded greedy requests over 4 slots.  Checks: every request
         ends ``ok`` with its full budget and no non-finite sentinel, and
         nothing is compiled after ``prebuild()``.  Prints how many
         requests match the same prompts served one at a time.

Four chips (``--four-chips``)
  The faithful and the ZeRO flat engines on a (4, 1) data mesh, each
  against the faithful engine on one chip with the same global batch, loss
  for loss; then ``synk.function`` Scatter -> Reduce("mean") SGD against
  the serial program.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Without a TPU
the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.core as synk  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import ShapeConfig  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import local_mesh, single_device_mesh  # noqa: E402
from repro.models import registry  # noqa: E402
from repro.models.common import ShardRules  # noqa: E402
from repro.optim import OptConfig  # noqa: E402
from repro.serve import NONFINITE_TOKEN, EngineConfig, Router, RouterConfig  # noqa: E402
from repro.train.loop import init_sharded  # noqa: E402
from repro.train.step import TrainSettings, jit_train_step  # noqa: E402

ARCH = "smollm-360m"
SEED = 0
# train: global batch x sequence (peak ~11.9 GiB of the 16 GiB chip by the
# v5e compile's memory_analysis), steps, Adam learning rate
BATCH, SEQ, STEPS, LR = 8, 512, 5, 1e-3
# one Adam step through the kernel vs the jnp reference: both compute the
# same f32 expression, so they agree to rounding; 1e-5 is 1% of the
# ~lr-sized first update
PARAM_ATOL = 1e-5
# four-chip vs one-chip losses (the tolerance of md_checks.check_flat_parity)
LOSS_RTOL = 2e-3
# serve: requests, lanes, prompt lengths, new tokens per request
REQUESTS, SLOTS, PROMPT_MIN, PROMPT_MAX, NEW_TOKENS = 8, 4, 16, 128, 32


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# Train
# ---------------------------------------------------------------------------


def seeded_tokens(cfg, batch: int, seq: int) -> np.ndarray:
    rng = np.random.default_rng(SEED)
    return rng.integers(0, cfg.vocab, size=(batch, seq + 1), dtype=np.int32)


def train_run(cfg, mesh, settings: TrainSettings, tokens: np.ndarray,
              steps: int) -> dict:
    """Compile the train step for ``mesh`` and take ``steps`` steps on the
    same batch from the seeded init.  Returns the engine name, the compiled
    step, compile seconds, losses, per-step seconds and the parameters after
    the first step (host copy)."""
    rules = ShardRules.for_mesh(mesh, faithful=settings.faithful)
    opt = OptConfig(kind="adam", lr=LR)
    shape = ShapeConfig("chip_smoke", "train", tokens.shape[1] - 1,
                        tokens.shape[0])
    stepf, sds, in_sh = jit_train_step(cfg, mesh, rules, opt, shape, settings)
    t0 = time.perf_counter()
    compiled = stepf.lower(*sds).compile()
    compile_s = time.perf_counter() - t0
    params, opt_state = init_sharded(cfg, mesh, rules, opt, SEED, settings)
    batch = {"tokens": jax.device_put(tokens, in_sh[2]["tokens"])}
    losses, times, first = [], [], None
    for i in range(steps):
        t0 = time.perf_counter()
        params, opt_state, metrics = compiled(params, opt_state, batch)
        losses.append(float(metrics["loss"]))      # waits for the step
        times.append(time.perf_counter() - t0)
        if i == 0:
            first = jax.device_get(params)
    return {"engine": stepf._flat_engine, "compiled": compiled,
            "compile_s": compile_s, "losses": losses, "times": times,
            "first": first}


def max_abs_diff(a, b) -> float:
    return max(float(np.max(np.abs(np.asarray(x, np.float32)
                                   - np.asarray(y, np.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def train_phase(cfg, batch: int = BATCH, seq: int = SEQ,
                steps: int = STEPS) -> dict:
    tokens = seeded_tokens(cfg, batch, seq)
    mesh = local_mesh(data=1)
    ref = train_run(cfg, mesh, TrainSettings(faithful=True, flat_kernel=False),
                    tokens, 1)
    run = train_run(cfg, mesh, TrainSettings(faithful=True), tokens, steps)
    losses = run["losses"]
    log(f"train: engine={run['engine']} batch={batch} seq={seq} "
        f"losses={losses}")
    check(run["engine"] == "faithful",
          f"train step lowered to {run['engine']!r}, not the faithful engine")
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    diff = max_abs_diff(run["first"], ref["first"])
    log(f"train: kernel vs reference Adam, params after one step: "
        f"max |diff| = {diff:.3e} (tolerance {PARAM_ATOL:g})")
    check(diff <= PARAM_ATOL,
          f"kernel and reference Adam disagree: {diff} > {PARAM_ATOL}")
    log(f"train (informational): compile {run['compile_s']:.1f} s kernel "
        f"step, {ref['compile_s']:.1f} s reference step; median step "
        f"{statistics.median(run['times'][1:] or run['times']) * 1e3:.1f} ms "
        f"over steps 2..{steps}")
    return {"kernel_text": run["compiled"].as_text(),
            "reference_text": ref["compiled"].as_text()}


# ---------------------------------------------------------------------------
# Serve
# ---------------------------------------------------------------------------


def serve_phase(cfg) -> None:
    mesh = single_device_mesh()
    rules = ShardRules.for_mesh(mesh)
    mod = registry.get_module(cfg)
    params = jax.jit(mod.init, static_argnums=0)(cfg, jax.random.PRNGKey(SEED))
    router = Router(
        cfg, mesh, rules, params,
        EngineConfig(max_slots=SLOTS, max_len=PROMPT_MAX + NEW_TOKENS + 8,
                     seed=SEED),
        RouterConfig(replicas=1),
    )
    del params
    t0 = time.perf_counter()
    router.prebuild()
    builds = router.aot.stats["builds"]
    log(f"serve (informational): prebuild {builds} executables in "
        f"{time.perf_counter() - t0:.1f} s")

    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab, int(n), dtype=np.int32)
               for n in rng.integers(PROMPT_MIN, PROMPT_MAX + 1, REQUESTS)]
    t0 = time.perf_counter()
    for rid, p in enumerate(prompts):
        router.submit(p, max_new_tokens=NEW_TOKENS, temperature=0.0, rid=rid)
    router.run()
    wall = time.perf_counter() - t0
    batched = {rid: router.completions[rid] for rid in range(REQUESTS)}
    for rid, c in batched.items():
        log(f"serve: req{rid} {c.status} plen={c.prompt_len} "
            f"new={len(c.tokens)} {c.tokens[:8]}...")
    ok = [c for c in batched.values()
          if c.status == "ok" and len(c.tokens) == NEW_TOKENS
          and NONFINITE_TOKEN not in c.tokens]
    log(f"serve: {len(ok)}/{REQUESTS} requests ok with {NEW_TOKENS} tokens "
        f"({REQUESTS * NEW_TOKENS} tokens in {wall:.2f} s, informational)")
    check(len(ok) == REQUESTS,
          f"only {len(ok)}/{REQUESTS} requests ended ok with a full budget")

    # the same prompts one at a time, through the same executables
    alone = {}
    for rid, p in enumerate(prompts):
        router.submit(p, max_new_tokens=NEW_TOKENS, temperature=0.0,
                      rid=REQUESTS + rid)
        router.run()
        alone[rid] = router.completions[REQUESTS + rid]
    steady = router.aot.stats["builds"] - builds
    log(f"serve: {steady} executable builds after prebuild()")
    check(steady == 0, f"{steady} executables built after prebuild()")
    same = [rid for rid in range(REQUESTS)
            if alone[rid].tokens == batched[rid].tokens]
    log(f"serve: {len(same)}/{REQUESTS} requests match, token for token, "
        f"the same prompt served alone"
        + ("" if len(same) == REQUESTS else
           f"; differ: {sorted(set(range(REQUESTS)) - set(same))}"))


# ---------------------------------------------------------------------------
# Four chips: the paper's data-parallel path
# ---------------------------------------------------------------------------


def four_chip_phase(cfg) -> None:
    tokens = seeded_tokens(cfg, BATCH, SEQ)
    steps = 3
    one = train_run(cfg, local_mesh(data=1), TrainSettings(faithful=True),
                    tokens, steps)["losses"]
    log(f"four-chips: one chip, faithful: losses={one}")
    mesh4 = local_mesh(data=4)
    for name, settings in (("faithful", TrainSettings(faithful=True)),
                           ("zero", TrainSettings(flat_engine="zero"))):
        run = train_run(cfg, mesh4, settings, tokens, steps)
        got = run["losses"]
        rel = max(abs(a - b) / abs(b) for a, b in zip(got, one))
        log(f"four-chips: (4, 1) mesh, {name}: engine={run['engine']} "
            f"losses={got} max rel diff vs one chip {rel:.3e} "
            f"(tolerance {LOSS_RTOL:g})")
        check(run["engine"] == name, f"{name} lowered to {run['engine']!r}")
        check("tpu_custom_call" in run["compiled"].as_text(),
              f"{name}: compiled step holds no Pallas kernel")
        check(rel <= LOSS_RTOL, f"{name}: losses {got} vs one chip {one}")
    check(sgd_parity(), "synk SGD parity failed")


def sgd_parity() -> bool:
    """Paper Appendix A: SGD with a Scatter -> Reduce("mean") gradient over
    every chip equals the serial single-device program."""
    ctx = synk.fork()
    rng = np.random.default_rng(3)
    X = rng.normal(size=(64, 8)).astype(np.float32)
    Y = (X @ rng.normal(size=(8,)) + 0.1).astype(np.float32)
    w0 = rng.normal(size=(8,)).astype(np.float32)
    lr = 0.05

    def grad_fn(x, y, w):
        return jax.grad(lambda w: jnp.mean((x @ w - y) ** 2))(w)

    # f32 matmuls at full precision on both sides: the check is about the
    # data-parallel program, not the MXU's default bf16 passes
    with jax.default_matmul_precision("highest"):
        serial = jax.jit(grad_fn)
        w_ref = w0.copy()
        for _ in range(5):
            w_ref = w_ref - lr * np.asarray(serial(X, Y, w_ref))
        f = synk.function(grad_fn, [synk.Scatter(), synk.Scatter(),
                                    synk.Broadcast()], synk.Reduce("mean"))
        w = w0.copy()
        for _ in range(5):
            w = w - lr * np.asarray(f(X, Y, w))
    err = float(np.max(np.abs(w - w_ref) / np.abs(w_ref)))
    log(f"four-chips: synk SGD over {ctx.n_data} workers vs serial: "
        f"max rel diff {err:.3e} (tolerance 1e-5)")
    return err <= 1e-5


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip data-parallel path")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {len(devices)} "
              f"{dev.platform} device(s) ({dev.device_kind})", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}")
    cfg = get_config(ARCH)
    log(f"model: {cfg.name} {cfg.n_layers}L d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv} d_ff={cfg.d_ff} vocab={cfg.vocab}")
    try:
        if args.four_chips:
            four_chip_phase(cfg)
        else:
            texts = train_phase(cfg)
            check("tpu_custom_call" in texts["kernel_text"],
                  "kernel train step holds no Pallas kernel (tpu_custom_call)")
            check("tpu_custom_call" not in texts["reference_text"],
                  "reference train step unexpectedly holds a Pallas kernel")
            log("train: compiled step holds the Pallas flat-Adam kernel; "
                "the reference step does not")
            del texts
            serve_phase(cfg)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
