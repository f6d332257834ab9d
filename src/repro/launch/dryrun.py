import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any jax import: jax locks the device
count at backend init, and the production meshes need 512 host devices.

Usage:
    python -m repro.launch.dryrun --arch deepseek-67b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both --out artifacts/dryrun

Each cell writes ``<out>/<arch>__<shape>__<mesh>[__faithful].json`` with
memory_analysis, cost_analysis, collective stats and the roofline terms.
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax

from repro.configs import ARCH_IDS, SHAPES, get_config, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.models.common import ShardRules
from repro.optim import OptConfig
from repro.roofline import V5E, summarize_cell
from repro.serve.step import jit_decode_step, jit_prefill
from repro.train.step import TrainSettings, jit_train_step

# paper §5.1: input slicing is the OOM-avoidance knob; per-arch defaults
# chosen so the train_4k activations fit 16 GB/chip (see EXPERIMENTS.md).
TRAIN_SLICES = {
    "deepseek-67b": 8,
    "internvl2-76b": 8,
    "qwen3-moe-235b-a22b": 16,
    "gemma2-27b": 4,
    "stablelm-12b": 4,
    "qwen3-moe-30b-a3b": 4,
    "smollm-360m": 4,
    "whisper-tiny": 4,
    "zamba2-1.2b": 8,
    "xlstm-1.3b": 8,
}

# sequence-parallel residual stream only helps attention-family archs;
# SSM/recurrent blocks shard their head/channel dims instead (DESIGN.md).
NO_SP = ("hybrid", "ssm")


def cell_name(arch: str, shape: str, mesh: str, faithful: bool,
              variants: tuple[str, ...] = ()) -> str:
    n = f"{arch}__{shape}__{mesh}"
    if faithful:
        n += "__faithful"
    if variants:
        n += "__v-" + "-".join(variants)
    return n


def make_rules(mesh, cfg, faithful: bool) -> ShardRules:
    rules = ShardRules.for_mesh(mesh, faithful=faithful)
    if cfg.family in NO_SP:
        rules = dataclasses.replace(rules, sp=False)
    return rules


def serving_config(cfg):
    """Serving stores parameters in bf16 (no optimizer aboard)."""
    return dataclasses.replace(cfg, param_dtype="bfloat16")


def apply_variants(cfg, rules, settings_kw: dict, variants: tuple[str, ...]):
    """Named hillclimb variants (EXPERIMENTS.md §Perf):

    pure_dp     — no tensor parallelism: every mesh axis is data-parallel
                  (the paper's native mode; optimal when the model fits a chip)
    bf16_params — store parameters in bf16 (fp32 Adam moments = master)
    remat_dots  — checkpoint policy saves matmul outputs (skip bwd recompute)
    accum_bf16  — bf16 microbatch gradient accumulator
    moe_cf10    — MoE capacity factor 1.0 (smaller dispatch buffers)
    """
    for v in variants:
        if v == "pure_dp":
            rules = dataclasses.replace(
                rules, dp=tuple(rules.mesh.axis_names), tp=None,
                fsdp="data", sp=False)
        elif v == "bf16_params":
            cfg = dataclasses.replace(cfg, param_dtype="bfloat16")
        elif v == "remat_dots":
            settings_kw["remat"] = "dots"
        elif v == "remat_none":
            settings_kw["remat"] = False
        elif v == "accum_bf16":
            settings_kw["accum_dtype"] = "bfloat16"
        elif v == "opt_scan":
            settings_kw["opt_chunked"] = True
        elif v == "moe_cf10":
            cfg = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=1.0))
        elif v:
            raise ValueError(f"unknown variant {v!r}")
    return cfg, rules, settings_kw


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             faithful: bool = False, num_slices: int | None = None,
             variants: tuple[str, ...] = ()) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    rules = make_rules(mesh, cfg, faithful)
    settings_kw: dict = {}
    cfg, rules, settings_kw = apply_variants(cfg, rules, settings_kw, variants)

    t0 = time.perf_counter()
    if shape.kind == "train":
        k = num_slices if num_slices is not None else TRAIN_SLICES.get(arch, 1)
        # each microbatch must still cover every data-parallel shard
        ndp = 1
        for a in rules.dp:
            ndp *= mesh.shape[a]
        k = max(1, min(k, shape.global_batch // max(ndp, 1)))
        opt_chunked = settings_kw.pop("opt_chunked", False)
        settings = TrainSettings(num_slices=k, faithful=faithful, **settings_kw)
        jitted, (p_sds, o_sds, b_sds), _ = jit_train_step(
            cfg, mesh, rules, OptConfig(kind="adam", chunked=opt_chunked),
            shape, settings
        )
        lowered = jitted.lower(p_sds, o_sds, b_sds)
    elif shape.kind == "prefill":
        scfg = serving_config(cfg)
        jitted, (p_sds, tok_sds, e_sds) = jit_prefill(
            scfg, mesh, rules, shape, max_len=shape.seq_len
        )
        lowered = jitted.lower(p_sds, tok_sds, e_sds)
    else:  # decode
        scfg = serving_config(cfg)
        jitted, (p_sds, cache_sds, tok_sds, idx_sds) = jit_decode_step(
            scfg, mesh, rules, shape
        )
        lowered = jitted.lower(p_sds, cache_sds, tok_sds, idx_sds)
    t_lower = time.perf_counter() - t0

    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    out = summarize_cell(cfg, shape, cost, mem, hlo, n_chips, V5E)
    out.update({
        "mesh": "multi" if multi_pod else "single",
        "faithful": faithful,
        "variants": list(variants),
        "num_slices": num_slices if num_slices is not None else TRAIN_SLICES.get(arch, 1),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
    })
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"), default="single")
    ap.add_argument("--faithful", action="store_true",
                    help="paper-faithful replicated-parameter DP baseline")
    ap.add_argument("--variant", default="",
                    help="comma-joined hillclimb variants (see apply_variants)")
    ap.add_argument("--slices", type=int, default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)

    os.makedirs(args.out, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                for m in meshes:
                    cells.append((a, s, m))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required without --all")
        cells = [(args.arch, args.shape, m) for m in meshes]

    variants = tuple(v for v in args.variant.split(",") if v)
    failures = 0
    for arch, shape, m in cells:
        name = cell_name(arch, shape, m, args.faithful, variants)
        path = os.path.join(args.out, name + ".json")
        if args.skip_existing and os.path.exists(path):
            print(f"[dryrun] {name}: exists, skipping")
            continue
        print(f"[dryrun] {name}: lowering...", flush=True)
        try:
            res = run_cell(arch, shape, m == "multi",
                           faithful=args.faithful, num_slices=args.slices,
                           variants=variants)
        except Exception as e:
            failures += 1
            res = {"arch": arch, "shape": shape, "mesh": m,
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
            print(f"[dryrun] {name}: FAILED {type(e).__name__}: {e}", flush=True)
        with open(path, "w") as f:
            json.dump(res, f, indent=1)
        if "error" not in res:
            if res.get("skipped"):
                print(f"[dryrun] {name}: skipped ({res['skipped']})")
            else:
                t = res["terms"]
                print(
                    f"[dryrun] {name}: ok compile={res['compile_s']}s "
                    f"compute={t['compute_s']:.4f}s memory={t['memory_s']:.4f}s "
                    f"coll={t['collective_s']:.4f}s dom={t['dominant']} "
                    f"peak={res.get('memory', {}).get('peak_estimate_bytes', 0)/2**30:.2f}GiB",
                    flush=True,
                )
        jax.clear_caches()
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
