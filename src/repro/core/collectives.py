"""MPI-like collectives over replicated/per-worker state (paper §3.2).

The paper manages one copy of every Theano shared variable per GPU and
exposes NCCL collectives (broadcast, all-reduce, scatter, gather) plus
get/set on individual devices.  The JAX analogue distinguishes two layouts:

* **Replicated state** (a plain pytree with replicated sharding): under
  SPMD there is one logical copy, so ``broadcast`` is ``distribute`` and
  ``all_reduce`` is the identity.  Used by the ``gspmd`` path.

* **Per-worker state** (:class:`LocalValues`): arrays with an explicit
  leading worker axis sharded over the data axes — the honest encoding of
  the paper's "updates are applied only locally within each GPU".  The
  collectives below reproduce NCCL semantics across that axis.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from . import context as ctx_mod

_OPS = ("avg", "mean", "sum", "max", "min", "prod")


@dataclasses.dataclass
class LocalValues:
    """A pytree with one value per data-parallel worker.

    Every leaf has leading dim == n_workers, sharded over the data axes, so
    worker *i*'s copy lives in worker *i*'s memory — the paper's replicated
    shared variables.
    """

    tree: Any

    def local(self, fn_tree=None):
        return self.tree


def distribute(tree: Any, ctx: ctx_mod.SynkContext | None = None) -> LocalValues:
    """Paper's ``synk.distribute()``: replicate state onto every worker.

    Returns per-worker copies (LocalValues) so that subsequent local updates
    may diverge, exactly as Theano shared variables replicated per GPU do.
    """
    ctx = ctx or ctx_mod.current()
    n = ctx.n_data

    def rep(x):
        x = jnp.asarray(x)
        stacked = jnp.broadcast_to(x[None], (n,) + x.shape)
        return jax.device_put(stacked, ctx.sharding(ctx.data_spec(*([None] * x.ndim))))

    return LocalValues(jax.tree.map(rep, tree))


def replicate(tree: Any, ctx: ctx_mod.SynkContext | None = None) -> Any:
    """Single-copy replication (gspmd path): one logical array, replicated
    sharding across the whole mesh."""
    ctx = ctx or ctx_mod.current()
    return jax.tree.map(lambda x: jax.device_put(jnp.asarray(x), ctx.sharding(P())), tree)


# ---------------------------------------------------------------------------
# NCCL-style collectives over LocalValues
# ---------------------------------------------------------------------------

def _shard_mapped(op_fn, ctx: ctx_mod.SynkContext):
    daxes = ctx.data_axes

    def per_leaf(x):
        spec = P(daxes, *([None] * (x.ndim - 1)))

        def dev(v):
            # v: (1, ...) local block
            return op_fn(v, daxes)

        return jax.jit(
            jax.shard_map(dev, mesh=ctx.mesh, in_specs=spec, out_specs=spec)
        )(x)

    return per_leaf


def all_reduce(values: LocalValues, op: str = "avg", ctx=None) -> LocalValues:
    """Paper's ``synk.all_reduce``: combine all workers' copies in place.

    After this call every worker holds the reduced value (NCCL all-reduce).
    """
    ctx = ctx or ctx_mod.current()
    if op not in _OPS:
        raise ValueError(f"op {op!r} not in {_OPS}")

    def op_fn(v, daxes):
        if op in ("avg", "mean"):
            return jax.lax.pmean(v, daxes)
        if op == "sum":
            return jax.lax.psum(v, daxes)
        if op == "max":
            return jax.lax.pmax(v, daxes)
        if op == "min":
            return jax.lax.pmin(v, daxes)
        if op == "prod":
            return jnp.exp(jax.lax.psum(jnp.log(v), daxes))
        raise AssertionError(op)

    f = _shard_mapped(op_fn, ctx)
    return LocalValues(jax.tree.map(f, values.tree))


def broadcast(values: LocalValues, root: int = 0, ctx=None) -> LocalValues:
    """NCCL broadcast: overwrite all workers' copies with ``root``'s."""
    ctx = ctx or ctx_mod.current()

    def per_leaf(x):
        src = x[root]
        n = x.shape[0]
        stacked = jnp.broadcast_to(src[None], (n,) + src.shape)
        return jax.device_put(
            stacked, ctx.sharding(ctx.data_spec(*([None] * src.ndim)))
        )

    return LocalValues(jax.tree.map(per_leaf, values.tree))


def reduce_to(values: LocalValues, op: str = "avg", root: int = 0, ctx=None) -> Any:
    """NCCL reduce: combine copies, return the (host-visible) root value."""
    red = all_reduce(values, op=op, ctx=ctx)
    return jax.tree.map(lambda x: x[root], red.tree)


def gather(values: LocalValues, ctx=None) -> Any:
    """Gather per-worker copies to the master (host): leading worker axis."""
    return jax.tree.map(np.asarray, values.tree)


def get_value(values: LocalValues, rank: int) -> Any:
    """Paper: 'get ... values on any individual GPU'."""
    return jax.tree.map(lambda x: np.asarray(x[rank]), values.tree)


def set_value(values: LocalValues, rank: int, new: Any) -> LocalValues:
    """Paper: 'set values on any individual GPU'."""
    def per_leaf(x, v):
        return x.at[rank].set(jnp.asarray(v))

    return LocalValues(jax.tree.map(per_leaf, values.tree, new))


def scatter_shared(tree: Any, ctx=None) -> LocalValues:
    """Paper §4.2: split arrays by first axis into per-worker shared state."""
    ctx = ctx or ctx_mod.current()
    n = ctx.n_data

    def per_leaf(x):
        x = jnp.asarray(x)
        if x.shape[0] % n != 0:
            raise ValueError(
                f"scatter_shared: leading dim {x.shape[0]} not divisible by {n}"
            )
        y = x.reshape((n, x.shape[0] // n) + x.shape[1:])
        return jax.device_put(
            y, ctx.sharding(ctx.data_spec(*([None] * (y.ndim - 1))))
        )

    return LocalValues(jax.tree.map(per_leaf, tree))


def as_replicated(values: LocalValues, check: bool = True) -> Any:
    """Collapse per-worker copies to one logical tree (after an all-reduce
    or broadcast made them identical)."""
    def per_leaf(x):
        if check:
            first = x[0]
            if not bool(jnp.all(jnp.isclose(x, first[None]) | ~jnp.isfinite(x) & ~jnp.isfinite(first[None]))):
                raise ValueError("worker copies diverged; all_reduce/broadcast first")
        return x[0]

    return jax.tree.map(per_leaf, values.tree)
