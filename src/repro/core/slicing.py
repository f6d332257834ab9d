"""Automated input slicing with aggregation (paper §5.1).

When a function call is too large for one device invocation, the worker
computes its result by scanning over ``num_slices`` subsets of its assigned
data and aggregating in place on the device.  Aggregation follows each
output's reduce spec; results are reduced across workers only once, after
the scan (paper: "Slice results are aggregated in-place on the GPU. Worker
results are reduced once back to the master process").

All slices see the *original* values of broadcast inputs (paper: "all
slices are computed using the original values, with updates accumulated and
applied only once at the end") — i.e. this is gradient accumulation when
the sliced function computes gradients.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from .specs import Reduce


def _split_leading(x, k: int):
    b = x.shape[0]
    if b % k != 0:
        raise ValueError(
            f"num_slices={k} must divide the per-worker batch {b} "
            f"(paper pads inputs 'as equal as possible'; pass a divisible batch)"
        )
    return x.reshape((k, b // k) + x.shape[1:])


def _acc_init(shape_dtype: jax.ShapeDtypeStruct, op: str | None):
    if op == "max":
        return jnp.full(shape_dtype.shape, -jnp.inf, shape_dtype.dtype)
    if op == "min":
        return jnp.full(shape_dtype.shape, jnp.inf, shape_dtype.dtype)
    # mean / sum accumulate in the output dtype; float accumulators promoted
    # to f32 to avoid bf16 drift across many slices.
    dt = shape_dtype.dtype
    if op in ("mean", "sum") and dt in (jnp.bfloat16, jnp.float16):
        dt = jnp.float32
    return jnp.zeros(shape_dtype.shape, dt)


def _acc_update(acc, val, op: str | None, k: int):
    if op == "mean":
        return acc + val.astype(acc.dtype) / k
    if op == "sum":
        return acc + val.astype(acc.dtype)
    if op == "max":
        return jnp.maximum(acc, val)
    if op == "min":
        return jnp.minimum(acc, val)
    raise AssertionError(op)


def sliced_call(
    fn: Callable,
    args: Sequence[Any],
    sliced_mask: Sequence[bool],
    out_ops: Any,               # pytree of Reduce matching fn's output
    num_slices: int,
    vary_axes: tuple[str, ...] = (),
):
    """Run ``fn(*args)`` as a ``lax.scan`` over ``num_slices`` slices.

    ``sliced_mask[i]`` — whether args[i] is split along its leading axis.
    Outputs with op mean/sum/max/min are accumulated; ``concat``/``None``
    outputs are stacked and re-flattened; ``last`` keeps the final slice.
    """
    k = num_slices
    split_args = [
        jax.tree.map(lambda x: _split_leading(x, k), a) if m else a
        for a, m in zip(args, sliced_mask)
    ]

    # Discover output structure abstractly.
    def first_slice(a, m):
        return jax.tree.map(lambda x: x[0], a) if m else a

    probe_args = [first_slice(a, m) for a, m in zip(split_args, sliced_mask)]
    out_shape = jax.eval_shape(fn, *probe_args)
    out_leaves, out_tree = jax.tree.flatten(out_shape)
    op_leaves = _flatten_ops(out_ops, out_tree)

    def _vary(x):
        # Inside shard_map, carries must match the per-slice outputs' varying
        # manual axes (data-derived values vary over the data axes).
        return jax.lax.pcast(x, vary_axes, to="varying") if vary_axes else x

    acc_init = [
        _vary(_acc_init(sd, op.op)) if op.op in ("mean", "sum", "max", "min") else None
        for sd, op in zip(out_leaves, op_leaves)
    ]
    last_init = [
        _vary(jnp.zeros(sd.shape, sd.dtype)) if op.op == "last" else None
        for sd, op in zip(out_leaves, op_leaves)
    ]

    def body(carry, xs):
        accs, lasts = carry
        sl_args = []
        xs_iter = iter(xs)
        for a, m in zip(args, sliced_mask):
            sl_args.append(next(xs_iter) if m else a)
        out = fn(*sl_args)
        flat = jax.tree.flatten(out)[0]
        new_accs, new_lasts, ys = [], [], []
        for i, (val, op) in enumerate(zip(flat, op_leaves)):
            if op.op in ("mean", "sum", "max", "min"):
                new_accs.append(_acc_update(accs[i], val, op.op, k))
                new_lasts.append(lasts[i])
                ys.append(None)
            elif op.op == "last":
                new_accs.append(accs[i])
                new_lasts.append(val)
                ys.append(None)
            else:  # concat / None: stack slices
                new_accs.append(accs[i])
                new_lasts.append(lasts[i])
                ys.append(val)
        return (new_accs, new_lasts), ys

    xs = [a for a, m in zip(split_args, sliced_mask) if m]
    (accs, lasts), ys = jax.lax.scan(body, (acc_init, last_init), xs, length=k)

    out_flat = []
    for i, (sd, op) in enumerate(zip(out_leaves, op_leaves)):
        if op.op in ("mean", "sum", "max", "min"):
            out_flat.append(accs[i].astype(sd.dtype))
        elif op.op == "last":
            out_flat.append(lasts[i])
        else:  # (k, b/k, ...) -> (b, ...)
            y = ys[i]
            out_flat.append(y.reshape((y.shape[0] * y.shape[1],) + y.shape[2:]))
    return jax.tree.unflatten(out_tree, out_flat)


def _flatten_ops(out_ops, out_tree) -> list[Reduce]:
    """Broadcast a Reduce spec (single or pytree-PREFIX) over the output
    tree: a Reduce at an interior position applies to every leaf below it
    (so ``(Reduce("mean"), Reduce(None))`` matches ``(loss, params_dict)``)."""
    if isinstance(out_ops, Reduce):
        return [out_ops] * out_tree.num_leaves
    from jax.api_util import flatten_axes
    flat = flatten_axes("synk.function outputs", out_tree, out_ops)
    return [op if isinstance(op, Reduce) else Reduce(op) for op in flat]
