"""Shared model machinery: sharding rules, initialisation, norms.

Models are pure-functional: parameters are pytrees of arrays, every model
exposes ``param_specs`` (abstract ShapeDtypeStructs + PartitionSpecs, used
by the dry-run without allocating), ``init``, ``loss_fn``, ``prefill`` and
``decode_step``.

Sharding is expressed through :class:`ShardRules`, which maps *logical*
dimension names to mesh axes:

  ``dp``    — batch (data parallel; the paper's scatter axis)
  ``tp``    — tensor parallel (heads / ffn hidden / vocab / experts)
  ``fsdp``  — parameter & optimizer-state sharding (ZeRO; ``None`` in the
              paper-faithful replicated mode)
  ``sp``    — sequence-parallel residual stream between blocks
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# Sharding rules
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardRules:
    """Logical-axis -> mesh-axis mapping.

    ``faithful()`` reproduces the paper: parameters replicated over the
    data-parallel workers (no fsdp), gradients combined by an explicit
    all-reduce.  The default is the beyond-paper ZeRO/SP configuration.

    Carries the mesh so constraints lower to explicit ``NamedSharding``s
    (robust outside a ``with mesh:`` context, e.g. AOT dry-run lowering).
    """

    dp: tuple[str, ...] = ("pod", "data")
    tp: str | None = "model"
    fsdp: str | None = "data"
    sp: bool = True
    mesh: Any = None

    def axis(self, logical: str | None):
        if logical is None:
            return None
        if logical == "dp":
            return self.dp
        if logical == "tp":
            return self.tp
        if logical == "fsdp":
            return self.fsdp
        if logical == "sp":
            return self.tp if self.sp else None
        raise ValueError(f"unknown logical axis {logical!r}")

    def pspec(self, *logical: str | None) -> P:
        return P(*[self.axis(l) for l in logical])

    def axis_size(self, axes) -> int:
        if axes is None or self.mesh is None:
            return 1
        if isinstance(axes, str):
            axes = (axes,)
        n = 1
        for a in axes:
            n *= self.mesh.shape[a]
        return n

    @classmethod
    def faithful(cls, dp=("pod", "data"), tp="model", mesh=None) -> "ShardRules":
        return cls(dp=dp, tp=tp, fsdp=None, sp=False, mesh=mesh)

    @classmethod
    def for_mesh(cls, mesh, *, faithful: bool = False) -> "ShardRules":
        names = mesh.axis_names
        dp = tuple(a for a in ("pod", "data") if a in names)
        tp = "model" if "model" in names else None
        if faithful:
            return cls.faithful(dp=dp, tp=tp, mesh=mesh)
        return cls(dp=dp, tp=tp, fsdp="data" if "data" in names else None,
                   sp=tp is not None, mesh=mesh)


_MANUAL_MODE = False  # inside a per-worker shard_map program: constraints off


class manual_mode:
    """Trace-time switch disabling sharding constraints.

    The flat-gradient train step runs the model as an explicit per-worker
    program inside ``shard_map``; there the mesh axes are manual and
    ``with_sharding_constraint`` over them is meaningless (and rejected by
    some JAX versions).  Model code stays unchanged — ``constrain``/
    ``constrain_spec``/``wuse`` become identity while a ``manual_mode()``
    block is active during tracing."""

    def __enter__(self):
        global _MANUAL_MODE
        self._prev = _MANUAL_MODE
        _MANUAL_MODE = True
        return self

    def __exit__(self, *exc):
        global _MANUAL_MODE
        _MANUAL_MODE = self._prev
        return False


def in_manual_mode() -> bool:
    """Whether tracing is inside a per-worker ``manual_mode()`` program."""
    return _MANUAL_MODE


def constrain(x, rules: ShardRules, *logical: str | None):
    """``with_sharding_constraint`` by logical axes.

    Dims that don't divide their mesh axes fall back to replicated on that
    dim (deterministic — no silent exception swallowing)."""
    if _MANUAL_MODE:
        return x
    resolved = []
    for i, l in enumerate(logical):
        axes = rules.axis(l)
        if axes is not None and x.shape[i] % max(rules.axis_size(axes), 1) != 0:
            axes = None
        resolved.append(axes)
    spec = P(*resolved)
    if rules.mesh is not None:
        return jax.lax.with_sharding_constraint(x, NamedSharding(rules.mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)


def constrain_spec(x, mesh, spec: P):
    """with_sharding_constraint with an explicit PartitionSpec + mesh."""
    if _MANUAL_MODE:
        return x
    if mesh is not None:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)


def wuse(w, rules: ShardRules, *logical: str | None, dtype=None):
    """Cast a stored parameter to the compute dtype and re-pin its sharding.

    Without the re-pin, SPMD may place the FSDP all-gather on the *stored*
    (fp32) tensor and cast afterwards — doubling gather wire bytes.  Pinning
    the casted copy to the same logical sharding forces collectives to move
    the compute-dtype bytes."""
    if dtype is not None and w.dtype != dtype:
        # the barrier stops the backend from eliding/hoisting the cast above
        # the FSDP all-gather (XLA:CPU legalizes bf16 dots to f32 and would
        # otherwise gather fp32 weights — 2x wire)
        w = jax.lax.optimization_barrier(w.astype(dtype))
    return constrain(w, rules, *logical)


# ---------------------------------------------------------------------------
# Parameter declaration: shapes + shardings declared together
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    logical: tuple[str | None, ...]
    dtype: Any = jnp.float32
    init_scale: float | None = None   # None -> fan-in scaled normal

    def sds(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, self.dtype)

    def pspec(self, rules: ShardRules) -> P:
        return rules.pspec(*self.logical)


def spec_tree_to_sds(tree):
    return jax.tree.map(
        lambda s: s.sds(), tree, is_leaf=lambda x: isinstance(x, ParamSpec)
    )


def spec_tree_to_pspecs(tree, rules: ShardRules):
    return jax.tree.map(
        lambda s: s.pspec(rules), tree, is_leaf=lambda x: isinstance(x, ParamSpec)
    )


def init_param(key, spec: ParamSpec):
    if spec.init_scale == 0.0:
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init_scale is not None:
        return (spec.init_scale * jax.random.normal(key, spec.shape)).astype(spec.dtype)
    fan_in = spec.shape[-2] if len(spec.shape) >= 2 else max(spec.shape[-1], 1)
    scale = 1.0 / np.sqrt(fan_in)
    return (scale * jax.random.normal(key, spec.shape)).astype(spec.dtype)


def init_tree(key, tree):
    leaves, treedef = jax.tree.flatten(
        tree, is_leaf=lambda x: isinstance(x, ParamSpec)
    )
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef, [init_param(k, s) for k, s in zip(keys, leaves)]
    )


# ---------------------------------------------------------------------------
# Numerics
# ---------------------------------------------------------------------------


def decode_positions(cur_index, batch: int):
    """``(B, 1)`` int32 RoPE position row per sequence for a decode step.

    ``cur_index`` is either a scalar (classic batched decode: every
    sequence sits at the same position) or a ``(B,)`` vector (the serve
    engine's slotted cache: each slot is at its own length).  Both
    broadcast to one position column per row.
    """
    cur = jnp.asarray(cur_index, jnp.int32)
    return jnp.broadcast_to(cur, (batch,))[:, None]


def remat_wrap(body, remat):
    """Apply a rematerialisation policy to a scan body.

    remat: False | True (save nothing) | "dots" (save matmul outputs —
    trades activation memory for skipping recompute collectives)."""
    if remat == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    if remat:
        return jax.checkpoint(body)
    return body


def rms_norm(x, gamma, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + gamma.astype(jnp.float32))).astype(dt)


# activations by the names published configs give them (transformers'
# ACT2FN): "gelu" is the exact erf form, "gelu_pytorch_tanh" the tanh one
ACTIVATIONS = {
    "silu": jax.nn.silu,
    "gelu": functools.partial(jax.nn.gelu, approximate=False),
    "gelu_pytorch_tanh": functools.partial(jax.nn.gelu, approximate=True),
}


def softcap(x, cap: float):
    """Gemma-2 logit soft-capping: cap * tanh(x / cap)."""
    return cap * jnp.tanh(x / cap)


def swiglu(x, w_gate, w_up, w_down, rules: ShardRules | None = None):
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    h = jax.nn.silu(g) * u
    return jnp.einsum("...f,fd->...d", h, w_down)


def cross_entropy_loss(logits, labels, mask=None):
    """Mean token-level cross entropy; logits (..., V) fp32-promoted.

    The gold-logit pick is an iota-compare-select reduction (not
    ``take_along_axis``) so it partitions cleanly when V is sharded over
    the tp axis (XLA fuses it; the (.., V) one-hot is never materialised).
    """
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    V = logits.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    gold = jnp.sum(
        jnp.where(iota == labels[..., None], logits, 0.0), axis=-1
    )
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
