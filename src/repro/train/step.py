"""Train-step builder: loss -> grads -> update, with the paper's §5.1
input slicing (gradient accumulation), remat, ZeRO/FSDP or paper-faithful
replicated parameters, and donated buffers.

Two families of lowering:

* **Flat-gradient engine** (optim/buckets.py; engages on pure data-parallel
  meshes for adam/adamw) — the model runs as an explicit per-worker program
  under ``shard_map``; gradients are flattened into ONE fp32 buffer (paper
  §3.3) and reduced per ~4 MiB parameter-aligned bucket so the scheduler
  can overlap bucket collectives with remaining backward compute:

  - ``faithful=True``  — the paper's Appendix-A program: per-bucket
    all-reduce(mean), fused flat-Adam (Pallas kernel on TPU) on the
    replicated flat buffers.
  - ``flat_engine="zero"`` (with ``faithful=False``) — per-bucket
    reduce-scatter, sharded flat-Adam on the owned 1/N shard (ZeRO
    optimizer-state sharding: ``m``/``v`` are flat scattered buffers),
    per-bucket all-gather of updated parameters.

* **GSPMD path** (everything else: tensor/expert parallel meshes, MoE,
  non-adam rules) — ``jax.jit`` with sharded inputs; XLA places the
  collectives.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import registry
from repro.models import common as common_mod
from repro.models.common import ShardRules
from repro.optim import OptConfig, apply_update, init_state, state_pspecs
from repro.optim.buckets import (
    BucketLayout,
    bucketed_all_gather,
    bucketed_all_reduce,
    bucketed_reduce_scatter,
    flat_adam_apply,
    make_buckets,
    resolve_bucket_bytes,
    scatter_flat,
)
from repro.optim.flat import FlatLayout, flatten, make_layout, unflatten

_DATA_AXIS_CANDIDATES = ("pod", "data")


@dataclasses.dataclass(frozen=True)
class TrainSettings:
    num_slices: int = 1          # paper §5.1 automated input slicing
    remat: Any = True            # False | True | "dots" (see common.remat_wrap)
    faithful: bool = False       # paper-faithful replicated-DP mode
    accum_dtype: str = "float32" # microbatch gradient accumulator dtype
    # Flat-gradient bucket engine:
    #   "auto" — faithful mode lowers to the bucketed flat program whenever
    #            the mesh is pure-DP and the rule is adam/adamw;
    #            non-faithful mode keeps the GSPMD per-parameter path.
    #   "zero" — non-faithful mode ALSO goes flat: bucketed reduce-scatter,
    #            sharded flat-Adam state, bucketed all-gather (ZeRO).
    #   "off"  — never use the flat engine.
    flat_engine: str = "auto"
    # None: Pallas flat_adam kernel on TPU, jnp reference elsewhere.
    flat_kernel: bool | None = None
    # Flat-engine non-finite gradient guard: when the reduced flat
    # gradient buffer holds any NaN/Inf, the step becomes a bitwise no-op
    # on params AND optimizer state (step counter included) — a loss
    # spike can then never poison the Adam moments.  The verdict is
    # computed on the post-reduction buffer (faithful) or psum'd across
    # shards (ZeRO), so every worker skips or applies in lockstep.
    # Surfaced as metrics["skipped"]; the loop counts skipped_steps.
    skip_nonfinite: bool = True


def data_axes_of(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in _DATA_AXIS_CANDIDATES)


def flat_engine_mode(
    cfg: ArchConfig, mesh: Mesh, opt: OptConfig, settings: TrainSettings,
) -> str | None:
    """Which flat-engine program this (cfg, mesh, opt, settings) lowers to:
    ``"faithful"`` | ``"zero"`` | ``None`` (GSPMD path).

    ``flat_engine="auto"`` degrades silently (faithful mode uses the flat
    program whenever it can, everything else falls back to GSPMD), but an
    EXPLICIT ``flat_engine="zero"`` request raises when it cannot engage —
    silently handing back unsharded optimizer state would defeat the
    memory plan the caller asked for.
    """
    if settings.flat_engine not in ("auto", "zero", "off"):
        raise ValueError(f"flat_engine {settings.flat_engine!r}")
    if settings.flat_engine == "off":
        return None
    want_zero = settings.flat_engine == "zero"

    def unavailable(reason: str):
        if want_zero:
            raise ValueError(f"flat_engine='zero' unavailable: {reason}")
        return None

    if opt.kind not in ("adam", "adamw"):
        return unavailable(f"requires adam/adamw, got {opt.kind!r}")
    daxes = data_axes_of(mesh)
    if not daxes:
        return unavailable("mesh has no data-parallel axes")
    # pure data-parallel only: with a live model axis the per-parameter
    # shardings carry tensor-parallel structure a flat buffer would destroy
    if any(mesh.shape[a] > 1 for a in mesh.axis_names if a not in daxes):
        return unavailable("mesh has a live model axis")
    # MoE loss paths shard_map internally (models/moe.py) and cannot nest
    if cfg.family == "moe":
        return unavailable("MoE loss paths shard_map internally")
    if settings.faithful:
        if want_zero:
            raise ValueError(
                "flat_engine='zero' conflicts with faithful=True "
                "(faithful replicates optimizer state by definition)"
            )
        return "faithful"
    if want_zero:
        if len(daxes) != 1:
            # reduce-scatter over exactly one named axis (multi-axis
            # scatter ordering is version-dependent)
            return unavailable(
                f"needs exactly one data axis, mesh has {daxes}")
        return "zero"
    return None


def _split_batch(batch: dict, k: int) -> dict:
    def sp(x):
        b = x.shape[0]
        if b % k:
            raise ValueError(f"num_slices={k} must divide global batch {b}")
        return x.reshape((k, b // k) + x.shape[1:])

    return {n: sp(v) for n, v in batch.items()}


def _make_compute_grads(cfg, mesh, rules, settings):
    """Shared loss+grad (with §5.1 slicing) used by both lowerings."""
    mod = registry.get_module(cfg)

    def loss_for_grad(params, microbatch):
        loss, metrics = mod.loss_fn(
            cfg, mesh, rules, params, microbatch, remat=settings.remat
        )
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_for_grad, has_aux=True)

    def compute_grads(params, batch):
        k = settings.num_slices
        if k == 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return loss, metrics, grads

        slices = _split_batch(batch, k)
        adt = jnp.dtype(settings.accum_dtype)

        def body(carry, mb):
            loss_acc, m_acc, g_acc = carry
            (loss, metrics), grads = grad_fn(params, mb)
            g_acc = jax.tree.map(
                lambda a, g: a + g.astype(adt) / k, g_acc, grads
            )
            m_acc = jax.tree.map(lambda a, m: a + m / k, m_acc, metrics)
            return (loss_acc + loss / k, m_acc, g_acc), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, adt), params)
        probe = jax.eval_shape(
            lambda p, b: grad_fn(p, b)[0][1], params,
            jax.tree.map(lambda x: x[0], slices),
        )
        m0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), probe)
        (loss, metrics, grads), _ = jax.lax.scan(
            body, (jnp.float32(0.0), m0, g0), slices
        )
        grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)
        return loss, metrics, grads

    return compute_grads


def build_train_step(
    cfg: ArchConfig,
    mesh: Mesh,
    rules: ShardRules,
    opt: OptConfig,
    settings: TrainSettings = TrainSettings(),
) -> Callable:
    """Returns train_step(params, opt_state, batch) -> (params, opt_state, metrics).

    The returned callable carries introspection attributes:
    ``_flat_engine`` (None | "faithful" | "zero"), and when flat,
    ``_flat_layout`` / ``_flat_buckets``.
    """
    mode = flat_engine_mode(cfg, mesh, opt, settings)
    if mode is not None:
        return _build_flat_train_step(cfg, mesh, rules, opt, settings, mode)

    compute_grads = _make_compute_grads(cfg, mesh, rules, settings)

    def train_step(params, opt_state, batch):
        loss, metrics, grads = compute_grads(params, batch)
        params, opt_state, opt_metrics = apply_update(opt, params, grads, opt_state)
        return params, opt_state, {"loss": loss, **metrics, **opt_metrics}

    train_step._flat_engine = None
    return train_step


# ---------------------------------------------------------------------------
# Flat-gradient engine (paper §3.3 + bucketed collectives)
# ---------------------------------------------------------------------------


def flat_layout_for(cfg: ArchConfig) -> FlatLayout:
    return make_layout(registry.abstract_params(cfg))


def buckets_for(
    cfg: ArchConfig, mesh: Mesh, opt: OptConfig, *, n_shards: int = 1,
) -> BucketLayout:
    layout = flat_layout_for(cfg)
    return make_buckets(
        layout,
        bucket_bytes=resolve_bucket_bytes(
            opt.bucket_mb, group_size=n_shards,
            device_kind=mesh.devices.flat[0].device_kind),
        n_shards=n_shards,
    )


def _build_flat_train_step(cfg, mesh, rules, opt, settings, mode: str):
    compute_grads = _make_compute_grads(cfg, mesh, rules, settings)
    daxes = data_axes_of(mesh)
    n_data = int(np.prod([mesh.shape[a] for a in daxes], dtype=np.int64))
    layout = flat_layout_for(cfg)
    buckets = make_buckets(
        layout,
        bucket_bytes=resolve_bucket_bytes(
            opt.bucket_mb, group_size=n_data,
            device_kind=mesh.devices.flat[0].device_kind),
        n_shards=n_data if mode == "zero" else 1,
    )
    wd = opt.weight_decay if opt.kind == "adamw" else 0.0

    def _clip(gflat_sq_sum, g):
        norm = jnp.sqrt(gflat_sq_sum)
        scale = jnp.minimum(1.0, opt.grad_clip / jnp.maximum(norm, 1e-12))
        return g * scale, norm

    def worker(params, opt_state, batch):
        with common_mod.manual_mode():
            loss, metrics, grads = compute_grads(params, batch)
        loss = jax.lax.pmean(loss, daxes)
        metrics = jax.tree.map(lambda x: jax.lax.pmean(x, daxes), metrics)
        # named scopes tag the engine's parts in the compiled module's
        # metadata (op_name), where a trace reduction finds them by name
        with jax.named_scope("flat_engine.flatten"):
            gflat = flatten(layout, grads)
        step = opt_state["step"] + 1
        adam_kw = dict(
            lr=opt.lr, beta1=opt.beta1, beta2=opt.beta2, eps=opt.eps,
            weight_decay=wd, use_kernel=settings.flat_kernel,
        )

        if mode == "faithful":
            # Appendix A, bucketed: every worker ends with the full mean
            # gradient; update replicated flat p/m/v buffers in one pass.
            with jax.named_scope("flat_engine.allreduce"):
                gflat = bucketed_all_reduce(gflat, buckets, daxes, op="mean")
            with jax.named_scope("flat_engine.flatten"):
                pflat = flatten(layout, params)
                mflat = flatten(layout, opt_state["m"])
                vflat = flatten(layout, opt_state["v"])
            with jax.named_scope("flat_engine.adam"):
                # skip-step verdict AFTER the all-reduce: one worker's NaN
                # poisons every worker's mean, so the check is globally
                # consistent with no extra collective
                ok = jnp.all(jnp.isfinite(gflat)) \
                    if settings.skip_nonfinite else None
                if opt.grad_clip:
                    gflat, gnorm = _clip(jnp.sum(jnp.square(gflat)), gflat)
                    metrics = {**metrics, "grad_norm": gnorm}
                p2, m2, v2 = flat_adam_apply(
                    pflat, gflat, mflat, vflat, step, **adam_kw
                )
                if ok is not None:
                    # bitwise no-op on skip: keep the pre-update buffers
                    # and don't advance the Adam step counter (bias
                    # correction must not decay across a skipped step)
                    p2 = jnp.where(ok, p2, pflat)
                    m2 = jnp.where(ok, m2, mflat)
                    v2 = jnp.where(ok, v2, vflat)
                    step = opt_state["step"] + ok.astype(step.dtype)
                    metrics = {**metrics,
                               "skipped": 1.0 - ok.astype(jnp.float32)}
            with jax.named_scope("flat_engine.unflatten"):
                new_params = unflatten(layout, p2)
                new_state = {
                    "step": step,
                    "m": unflatten(layout, m2, dtype=jnp.float32),
                    "v": unflatten(layout, v2, dtype=jnp.float32),
                }
            return new_params, new_state, {"loss": loss, **metrics}

        # ZeRO: own 1/N of every bucket; m/v live scattered (flat, sharded)
        with jax.named_scope("flat_engine.allreduce"):
            g_loc = bucketed_reduce_scatter(
                gflat, buckets, daxes[0], op="mean")
        with jax.named_scope("flat_engine.flatten"):
            widx = jax.lax.axis_index(daxes[0])
            p_loc = scatter_flat(flatten(layout, params), buckets, widx)
        with jax.named_scope("flat_engine.adam"):
            # the scatter localizes a NaN to whichever shard owns that
            # region, so the skip verdict needs a psum'd count to stay in
            # lockstep
            ok = None
            if settings.skip_nonfinite:
                bad = jax.lax.psum(
                    jnp.sum((~jnp.isfinite(g_loc)).astype(jnp.int32)), daxes)
                ok = bad == 0
            if opt.grad_clip:
                g_loc, gnorm = _clip(
                    jax.lax.psum(jnp.sum(jnp.square(g_loc)), daxes), g_loc
                )
                metrics = {**metrics, "grad_norm": gnorm}
            p2, m2, v2 = flat_adam_apply(
                p_loc, g_loc, opt_state["m"], opt_state["v"], step, **adam_kw
            )
            if ok is not None:
                # params reassemble through all-gather of the (unchanged)
                # shard — pure data movement, so the round trip is bitwise
                p2 = jnp.where(ok, p2, p_loc)
                m2 = jnp.where(ok, m2, opt_state["m"])
                v2 = jnp.where(ok, v2, opt_state["v"])
                step = opt_state["step"] + ok.astype(step.dtype)
                metrics = {**metrics,
                           "skipped": 1.0 - ok.astype(jnp.float32)}
        with jax.named_scope("flat_engine.allreduce"):
            p_full = bucketed_all_gather(p2, buckets, daxes[0])
        with jax.named_scope("flat_engine.unflatten"):
            new_params = unflatten(layout, p_full)
        new_state = {"step": step, "m": m2, "v": v2}
        return new_params, new_state, {"loss": loss, **metrics}

    if mode == "faithful":
        opt_in = P()
        opt_out = P()
    else:
        opt_in = {"step": P(), "m": P(daxes), "v": P(daxes)}
        opt_out = {"step": P(), "m": P(daxes), "v": P(daxes)}

    mapped = jax.shard_map(
        worker,
        mesh=mesh,
        in_specs=(P(), opt_in, P(daxes)),
        out_specs=(P(), opt_out, P()),
        check_vma=False,
    )

    def train_step(params, opt_state, batch):
        return mapped(params, opt_state, batch)

    train_step._flat_engine = mode
    train_step._flat_layout = layout
    train_step._flat_buckets = buckets
    return train_step


# ---------------------------------------------------------------------------
# Optimizer-state construction (mode-aware: ZeRO flat state is scattered)
# ---------------------------------------------------------------------------


def opt_state_template(
    cfg: ArchConfig,
    mesh: Mesh,
    rules: ShardRules,
    opt: OptConfig,
    settings: TrainSettings = TrainSettings(),
):
    """Returns ``(init_fn(params) -> opt_state, state_pspecs_tree)``
    consistent with what :func:`build_train_step` will expect."""
    mode = flat_engine_mode(cfg, mesh, opt, settings)
    if mode == "zero":
        daxes = data_axes_of(mesh)
        n_data = int(np.prod([mesh.shape[a] for a in daxes], dtype=np.int64))
        buckets = buckets_for(cfg, mesh, opt, n_shards=n_data)
        n = buckets.scattered_total

        def init_fn(params):
            del params
            return {
                "step": jnp.zeros((), jnp.int32),
                "m": jnp.zeros((n,), jnp.float32),
                "v": jnp.zeros((n,), jnp.float32),
            }

        pspecs = {"step": P(), "m": P(daxes), "v": P(daxes)}
        return init_fn, pspecs
    p_pspecs = registry.param_pspecs(cfg, rules)
    return partial(init_state, opt), state_pspecs(opt, p_pspecs)


# ---------------------------------------------------------------------------
# Jitted assembly with shardings (the object the dry-run lowers)
# ---------------------------------------------------------------------------


def shardings_for(mesh: Mesh, pspecs):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )


def jit_train_step(
    cfg: ArchConfig,
    mesh: Mesh,
    rules: ShardRules,
    opt: OptConfig,
    shape: ShapeConfig,
    settings: TrainSettings = TrainSettings(),
    *,
    donate: bool = True,
):
    """Returns (jitted fn, (params_sds, opt_sds, batch_sds), in_shardings)."""
    step = build_train_step(cfg, mesh, rules, opt, settings)

    params_sds = registry.abstract_params(cfg)
    p_pspecs = registry.param_pspecs(cfg, rules)
    opt_init, o_pspecs = opt_state_template(cfg, mesh, rules, opt, settings)
    opt_sds = jax.eval_shape(opt_init, params_sds)
    batch_sds, b_pspecs = registry.train_inputs(cfg, shape, rules)

    in_sh = (
        shardings_for(mesh, p_pspecs),
        shardings_for(mesh, o_pspecs),
        shardings_for(mesh, b_pspecs),
    )
    out_sh = (in_sh[0], in_sh[1], None)
    jitted = jax.jit(
        step,
        in_shardings=in_sh,
        out_shardings=out_sh,
        donate_argnums=(0, 1) if donate else (),
    )
    jitted._flat_engine = getattr(step, "_flat_engine", None)
    jitted._flat_layout = getattr(step, "_flat_layout", None)
    jitted._flat_buckets = getattr(step, "_flat_buckets", None)
    return jitted, (params_sds, opt_sds, batch_sds), in_sh
