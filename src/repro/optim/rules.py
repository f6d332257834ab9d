"""Update rules (paper §3.3: the Lasagne rules adapted to multi-device —
SGD, Nesterov momentum, RMSProp, Adam) as pure pytree transforms.

States are fp32 regardless of parameter dtype (mixed-precision training);
with FSDP rules the states inherit the parameter shardings, which is
ZeRO-style optimizer-state sharding.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

KINDS = ("sgd", "momentum", "rmsprop", "adam", "adamw")


@dataclasses.dataclass(frozen=True)
class OptConfig:
    kind: str = "adam"
    lr: float = 3e-4
    momentum: float = 0.9
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip: float = 1.0
    # scan the update over each stacked leaf's layer axis: bounds the live
    # f32 temporaries of the elementwise update chain to one layer's worth
    # (the jnp mirror of the fused kernels/flat_adam pass; see §Perf)
    chunked: bool = False
    # flat-gradient bucket size (MiB) for the bucketed collective engine
    # (optim/buckets.py); parameter-boundary-aligned greedy partition.
    # "auto" sizes buckets from the interconnect model of the mesh's chip
    # (optim/buckets.resolve_bucket_bytes); a chip missing from the peak
    # table (roofline.analysis.PEAKS) raises.
    bucket_mb: float | str = 4.0

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"kind {self.kind!r} not in {KINDS}")
        if isinstance(self.bucket_mb, str):
            if self.bucket_mb != "auto":
                raise ValueError(
                    f"bucket_mb must be a float (MiB) or 'auto', "
                    f"got {self.bucket_mb!r}"
                )
        elif self.bucket_mb <= 0:
            raise ValueError(f"bucket_mb must be positive, got {self.bucket_mb}")


def init_state(cfg: OptConfig, params) -> dict:
    zeros = lambda: jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    st: dict[str, Any] = {"step": jnp.zeros((), jnp.int32)}
    if cfg.kind == "momentum":
        st["m"] = zeros()
    elif cfg.kind == "rmsprop":
        st["v"] = zeros()
    elif cfg.kind in ("adam", "adamw"):
        st["m"] = zeros()
        st["v"] = zeros()
    return st


def state_pspecs(cfg: OptConfig, param_pspecs) -> dict:
    """Optimizer-state shardings mirror the parameter shardings (ZeRO)."""
    from jax.sharding import PartitionSpec as P
    st: dict[str, Any] = {"step": P()}
    if cfg.kind == "momentum":
        st["m"] = param_pspecs
    elif cfg.kind == "rmsprop":
        st["v"] = param_pspecs
    elif cfg.kind in ("adam", "adamw"):
        st["m"] = param_pspecs
        st["v"] = param_pspecs
    return st


def global_norm(tree) -> jnp.ndarray:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), grads), norm


def apply_update(cfg: OptConfig, params, grads, state) -> tuple[Any, dict, dict]:
    """Returns (new_params, new_state, metrics)."""
    metrics = {}
    if cfg.grad_clip:
        grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
        metrics["grad_norm"] = gnorm
    step = state["step"] + 1
    lr = jnp.float32(cfg.lr)
    new_state: dict[str, Any] = {"step": step}

    def f32(x):
        return x.astype(jnp.float32)

    if cfg.kind == "sgd":
        upd = jax.tree.map(lambda g: lr * f32(g), grads)
    elif cfg.kind == "momentum":
        m = jax.tree.map(lambda m, g: cfg.momentum * m + f32(g), state["m"], grads)
        # Nesterov
        upd = jax.tree.map(lambda m, g: lr * (cfg.momentum * m + f32(g)), m, grads)
        new_state["m"] = m
    elif cfg.kind == "rmsprop":
        v = jax.tree.map(
            lambda v, g: cfg.beta2 * v + (1 - cfg.beta2) * jnp.square(f32(g)),
            state["v"], grads,
        )
        upd = jax.tree.map(lambda v, g: lr * f32(g) / (jnp.sqrt(v) + cfg.eps), v, grads)
        new_state["v"] = v
    elif cfg.chunked:  # adam/adamw, layer-scanned (bounded f32 temporaries)
        bc1 = 1 - cfg.beta1 ** step.astype(jnp.float32)
        bc2 = 1 - cfg.beta2 ** step.astype(jnp.float32)
        wd = cfg.weight_decay if cfg.kind == "adamw" else 0.0

        def leaf_update(p, g, m, v):
            def one(p, g, m, v):
                g = g.astype(jnp.float32)
                m = cfg.beta1 * m + (1 - cfg.beta1) * g
                v = cfg.beta2 * v + (1 - cfg.beta2) * jnp.square(g)
                u = lr * (m / bc1) / (jnp.sqrt(v / bc2) + cfg.eps)
                if wd:
                    u = u + lr * wd * p.astype(jnp.float32)
                return (p.astype(jnp.float32) - u).astype(p.dtype), m, v

            if p.ndim >= 2 and p.shape[0] > 1:
                # fori_loop over the (unsharded) stacked-layer axis with
                # in-place dynamic updates on the loop carry: bounds the
                # live f32 temps to one layer's slice WITHOUT the ys
                # double-buffer a scan would allocate — the jnp mirror of
                # the fused kernels/flat_adam pass
                def body(i, carry):
                    pc, mc, vc = carry
                    sl = lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
                    pn, mn, vn = one(sl(pc), sl(g), sl(mc), sl(vc))
                    up = lambda a, x: jax.lax.dynamic_update_index_in_dim(a, x, i, 0)
                    return up(pc, pn), up(mc, mn), up(vc, vn)

                return jax.lax.fori_loop(0, p.shape[0], body, (p, m, v))
            return one(p, g, m, v)

        out = jax.tree.map(leaf_update, params, grads, state["m"], state["v"])
        flat, _ = jax.tree.flatten(out, is_leaf=lambda x: isinstance(x, tuple))
        ptree = jax.tree.structure(params)
        new_params = jax.tree.unflatten(ptree, [o[0] for o in flat])
        new_state["m"] = jax.tree.unflatten(ptree, [o[1] for o in flat])
        new_state["v"] = jax.tree.unflatten(ptree, [o[2] for o in flat])
        return new_params, new_state, metrics
    else:  # adam / adamw
        m = jax.tree.map(lambda m, g: cfg.beta1 * m + (1 - cfg.beta1) * f32(g),
                         state["m"], grads)
        v = jax.tree.map(
            lambda v, g: cfg.beta2 * v + (1 - cfg.beta2) * jnp.square(f32(g)),
            state["v"], grads,
        )
        bc1 = 1 - cfg.beta1 ** step.astype(jnp.float32)
        bc2 = 1 - cfg.beta2 ** step.astype(jnp.float32)
        upd = jax.tree.map(
            lambda m, v: lr * (m / bc1) / (jnp.sqrt(v / bc2) + cfg.eps), m, v
        )
        new_state["m"], new_state["v"] = m, v

    if cfg.kind == "adamw" and cfg.weight_decay:
        upd = jax.tree.map(
            lambda u, p: u + lr * cfg.weight_decay * f32(p), upd, params
        )
    new_params = jax.tree.map(lambda p, u: (f32(p) - u).astype(p.dtype), params, upd)
    return new_params, new_state, metrics
