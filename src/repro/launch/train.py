"""Production training launcher.

    python -m repro.launch.train --arch deepseek-67b --shape train_4k \
        --mesh production --steps 1000 --ckpt-dir /ckpts/run1

On real hardware the mesh axes map onto the pod topology; on the dev box
use ``--mesh local`` (all local devices) or ``--mesh single``.  Restart
the same command after a failure: the loop resumes from the newest
checkpoint and replays the deterministic data stream.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax

from repro.configs import ARCH_IDS, SHAPES, get_config, get_smoke_config
from repro.configs.base import ShapeConfig
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import local_mesh, make_production_mesh, single_device_mesh
from repro.models.common import ShardRules
from repro.obs import Observer, Tracer, to_chrome_trace
from repro.optim import OptConfig
from repro.train import LoopConfig, TrainSettings, train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--shape", choices=list(SHAPES), default="train_4k")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + small shape (CPU dev)")
    ap.add_argument("--mesh", choices=("production", "multipod", "local", "single"),
                    default="local")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", choices=("adam", "adamw", "momentum",
                                            "rmsprop", "sgd"), default="adam")
    ap.add_argument("--slices", type=int, default=1,
                    help="paper §5.1 input slicing (gradient accumulation)")
    ap.add_argument("--faithful", action="store_true",
                    help="paper-faithful replicated-parameter DP")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="profile the host side of the loop: per-step "
                         "stage_batch/h2d/dispatch/device_wait spans + a "
                         "step_ms histogram, written as Chrome-trace JSON "
                         "(adds one host sync per step; see "
                         "docs/observability.md)")
    ap.add_argument("--jax-profile", default=None, metavar="DIR",
                    help="wrap the run in jax.profiler.start_trace/"
                         "stop_trace (device-side TensorBoard/Perfetto "
                         "trace); independent of --trace-out")
    args = ap.parse_args()
    enable_compile_cache()

    if args.mesh == "production":
        mesh = make_production_mesh()
    elif args.mesh == "multipod":
        mesh = make_production_mesh(multi_pod=True)
    elif args.mesh == "single":
        mesh = single_device_mesh()
    else:
        mesh = local_mesh()

    if args.smoke:
        cfg = get_smoke_config(args.arch)
        shape = ShapeConfig("smoke", "train", 64, 8)
    else:
        cfg = get_config(args.arch)
        shape = SHAPES[args.shape]

    rules = ShardRules.for_mesh(mesh, faithful=args.faithful)
    if cfg.family in ("hybrid", "ssm"):
        rules = dataclasses.replace(rules, sp=False)

    obs = Observer(tracer=Tracer(), name="train") if args.trace_out else None
    if args.jax_profile:
        jax.profiler.start_trace(args.jax_profile)

    res = train(
        cfg, shape, mesh, rules,
        OptConfig(kind=args.optimizer, lr=args.lr),
        TrainSettings(num_slices=args.slices, faithful=args.faithful),
        LoopConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                   ckpt_dir=args.ckpt_dir, seed=args.seed),
        obs=obs,
    )

    if args.jax_profile:
        jax.profiler.stop_trace()
        print(f"# jax profile written to {args.jax_profile}")
    if obs is not None:
        to_chrome_trace(obs.tracer.events, args.trace_out)
        hist = res["metrics"]["step_ms"]
        print(f"# step_ms p50/p99: {hist['p50']:.1f}/{hist['p99']:.1f} "
              f"over {hist['count']} steps -> trace {args.trace_out}")
    print(f"final loss: {res['final_loss']:.4f}")


if __name__ == "__main__":
    main()
