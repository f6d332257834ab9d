"""``synk.function`` — data-parallel execution of a serial function.

The user writes a serial ``fn`` over its batch of inputs; calling the
Synkhronos function induces the paper's §3.2 sequence:

  1) data inputs are scattered equally across workers,
  2) each worker calls the same function on its assigned data,
  3) results are reduced or gathered back and returned.

Two backends:

* ``shard_map`` (default, paper-faithful): an explicit per-worker program.
  Each device runs ``fn`` on its shard; outputs are combined with
  ``lax.pmean/psum/pmax/pmin/all_gather`` according to each output's
  :class:`Reduce` spec.  Updates to state are local per worker unless the
  user reduces them — exactly the paper's semantics.

* ``gspmd``: ``jax.jit`` with batch-sharded ``in_shardings``.  Here ``fn``
  is the *global* program and XLA inserts/overlaps collectives.  This is
  the beyond-paper optimized path used by the large-scale trainer.

Both support the paper's §5 extensions: ``num_slices=`` (automated input
slicing with aggregation) and ``batch=`` (input indexing, host- or
device-resident).

Dispatch is cheap: the per-call work is one signature probe over the raw
arguments.  Everything derivable from the signature — per-leaf target
shardings, the traced/compiled executable (AOT ``.lower().compile()``),
the output post-processing — is computed once per (shapes, dtypes,
treedefs, call options) and cached.  ``device_put`` is skipped for arrays
already resident with the target sharding, and ``donate=True`` donates
scattered input buffers to the executable — standard ``donate_argnums``
semantics: pass an already-staged device array to a donating function and
YOUR array is consumed (deleted after the call), exactly as with
``jax.jit``.  Host inputs are staged into fresh buffers each call and are
always safe to donate.

``batch=`` indices into a :class:`DeviceDataset` are **global** row ids
(the dataset's pre-scatter leading axis).  When each scattered index chunk
lands in its own worker's shard (e.g. per-worker shuffles), workers take
rows locally after rebasing to shard-local positions; otherwise rows are
routed between workers with a masked ``psum`` gather (correct for any
permutation, at the cost of one collective over the indexed batch).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from . import context as ctx_mod
from .aot import AotCache
from .data import DeviceDataset, SynkData, is_dataset, is_host_data
from .slicing import _flatten_ops, sliced_call
from .specs import Broadcast, Reduce, Scatter, canonicalize_in_spec, canonicalize_out_spec


@dataclasses.dataclass(frozen=True)
class _CallPlan:
    """Static description of one call signature (cache key companion)."""

    num_slices: int
    indexed: bool                    # batch= indices present
    routed: bool                     # device-resident indices cross shards
    dataset_arg: tuple[bool, ...]    # which args are DeviceDatasets
    ds_local_len: tuple[int | None, ...]  # per-arg local shard length


@dataclasses.dataclass
class _CacheEntry:
    plan: _CallPlan
    exe: Callable                    # AOT-compiled executable
    op_leaves: list | None = None    # output Reduce ops (filled on 1st call)


class SynkFunction:
    def __init__(
        self,
        fn: Callable,
        in_specs: Sequence[Any],
        out_specs: Any = Reduce("mean"),
        *,
        ctx: ctx_mod.SynkContext | None = None,
        backend: str = "shard_map",
        name: str | None = None,
        donate: bool = False,
    ):
        self.fn = fn
        self.in_specs = tuple(canonicalize_in_spec(s) for s in in_specs)
        self.out_specs = jax.tree.map(
            canonicalize_out_spec, out_specs,
            is_leaf=lambda x: isinstance(x, (Reduce, str)) or x is None,
        )
        self.ctx = ctx or ctx_mod.current()
        if backend not in ("shard_map", "gspmd"):
            raise ValueError(backend)
        self.backend = backend
        self.name = name or getattr(fn, "__name__", "synk_fn")
        self.donate = donate
        # AOT executables per call signature (shared cache class with the
        # serve engine; its builds/cache_hits counters feed self.stats)
        self.aot = AotCache(self.name)
        # shardings are signature-independent; precompute per (spec, ndim)
        self._sharding_cache: dict[tuple, NamedSharding] = {}
        self._counters = {"calls": 0, "device_puts": 0, "device_put_skips": 0}

    @property
    def stats(self) -> dict:
        """Dispatch counters (calls/builds/cache_hits/device_puts/...)."""
        return {**self._counters, **self.aot.stats}

    # ------------------------------------------------------------------
    def __call__(self, *args, num_slices: int = 1, batch=None):
        if len(args) != len(self.in_specs):
            raise TypeError(
                f"{self.name} takes {len(self.in_specs)} inputs, got {len(args)}"
            )
        self._counters["calls"] += 1
        ctx = self.ctx
        n = ctx.n_data
        dataset_arg = tuple(is_dataset(a) for a in args)
        indexed = batch is not None

        idx_global = None
        orig_len = None
        if indexed:
            idx_global = np.asarray(batch)
            if idx_global.ndim != 1:
                raise ValueError("batch= must be a 1-D index array")
            orig_len = idx_global.shape[0]
            if orig_len == 0:
                raise ValueError("batch= may not be empty")
            if orig_len % n != 0:
                idx_global = _pad_indices(idx_global, n)

        routed = False
        ds_local_len: list[int | None] = [None] * len(args)
        if indexed and any(dataset_arg):
            k = idx_global.shape[0] // n
            owners = np.repeat(np.arange(n), k)
            lo, hi = int(idx_global.min()), int(idx_global.max())
            for i, (a, is_ds) in enumerate(zip(args, dataset_arg)):
                if is_ds:
                    if lo < 0 or hi >= len(a):
                        raise IndexError(
                            f"batch= ids must be global dataset rows in "
                            f"[0, {len(a)}); got range [{lo}, {hi}]"
                        )
                    ds_local_len[i] = a.local_length
                    if self.backend == "shard_map" and not routed:
                        routed = bool(
                            np.any(idx_global // a.local_length != owners)
                        )

        plan = _CallPlan(
            num_slices=num_slices, indexed=indexed, routed=routed,
            dataset_arg=dataset_arg, ds_local_len=tuple(ds_local_len),
        )
        key = self._signature(args, idx_global, plan)
        staged, extra = self._stage_args(args, idx_global, plan)
        entry = self.aot.get(key, lambda: self._build_entry(plan, staged, extra))
        out = entry.exe(*staged, *extra)
        return self._postprocess(entry, out, orig_len)

    # ------------------------------------------------------------------
    # Signature & staging
    # ------------------------------------------------------------------
    def _signature(self, args, idx_global, plan: _CallPlan):
        """Cache key from the RAW args — no staging required first."""
        sig = []
        for a, is_ds in zip(args, plan.dataset_arg):
            if is_ds:
                sig.append(("ds", a.array.shape, str(a.array.dtype)))
            elif is_host_data(a):
                sig.append(("host", a.shape, str(a.dtype)))
            else:
                leaves, treedef = jax.tree.flatten(a)
                sig.append((
                    "tree", treedef,
                    tuple((np.shape(l), str(getattr(l, "dtype", np.asarray(l).dtype)))
                          for l in leaves),
                ))
        idx_len = idx_global.shape[0] if plan.indexed else None
        return (
            tuple(sig), plan.num_slices, plan.indexed, plan.routed,
            plan.dataset_arg, idx_len,
        )

    def _target_sharding(self, spec, ndim: int) -> NamedSharding:
        key = (isinstance(spec, Scatter), ndim)
        sh = self._sharding_cache.get(key)
        if sh is None:
            ctx = self.ctx
            if isinstance(spec, Scatter):
                sh = ctx.sharding(ctx.data_spec(*([None] * (ndim - 1))))
            else:
                sh = ctx.sharding(P())
            self._sharding_cache[key] = sh
        return sh

    def _put(self, arr, spec) -> jax.Array:
        """Stage one leaf, skipping device_put when already resident with
        the target sharding."""
        ctx = self.ctx
        if not isinstance(arr, jax.Array):
            arr = jnp.asarray(arr)
        if isinstance(spec, Scatter) and arr.shape[0] % ctx.n_data != 0:
            raise ValueError(
                f"scattered input batch {arr.shape[0]} must divide the "
                f"data-parallel worker count {ctx.n_data}"
            )
        target = self._target_sharding(spec, arr.ndim)
        if getattr(arr, "sharding", None) == target:
            self._counters["device_put_skips"] += 1
            return arr
        self._counters["device_puts"] += 1
        return jax.device_put(arr, target)

    def _stage_args(self, args, idx_global, plan: _CallPlan):
        staged = []
        for a, spec, is_ds in zip(args, self.in_specs, plan.dataset_arg):
            if is_ds:
                if not isinstance(spec, Scatter):
                    raise ValueError("DeviceDataset inputs must use Scatter spec")
                staged.append(a.array)  # already sharded on device
            elif is_host_data(a):
                arr = (
                    a.excerpt(idx_global)
                    if (plan.indexed and isinstance(spec, Scatter)) else a.array
                )
                staged.append(self._put(arr, spec))
            else:
                def prep(leaf):
                    if plan.indexed and isinstance(spec, Scatter):
                        leaf = np.asarray(leaf)[idx_global]
                    return leaf
                staged.append(jax.tree.map(
                    lambda leaf: self._put(prep(leaf), spec), a))
        extra = ()
        if plan.indexed and any(plan.dataset_arg):
            # Device-resident indexing (paper §5.2): global row ids, either
            # scattered (aligned fast path) or replicated (routed path).
            idx_spec = Broadcast() if plan.routed else Scatter()
            extra = (self._put(idx_global.astype(np.int32), idx_spec),)
        return staged, extra

    def _postprocess(self, entry: _CacheEntry, out, orig_len):
        """Slice padded ``concat`` outputs back to the request length."""
        if orig_len is None:
            return out
        leaves, tree = jax.tree.flatten(out)
        if entry.op_leaves is None:
            entry.op_leaves = _flatten_ops(self.out_specs, tree)
        if not any(op.op == "concat" for op in entry.op_leaves):
            return out
        cut = [
            (leaf[:orig_len] if op.op == "concat" and leaf.shape
             and leaf.shape[0] >= orig_len else leaf)
            for leaf, op in zip(leaves, entry.op_leaves)
        ]
        return jax.tree.unflatten(tree, cut)

    # ------------------------------------------------------------------
    # Build: trace + AOT-compile one executable per signature
    # ------------------------------------------------------------------
    def _build_entry(self, plan: _CallPlan, staged, extra) -> _CacheEntry:
        if self.backend == "shard_map":
            jitted = self._build_shard_map(plan, staged, extra)
        else:
            jitted = self._build_gspmd(plan, staged, extra)
        exe = jitted.lower(*staged, *extra).compile()
        return _CacheEntry(plan=plan, exe=exe)

    def _donate_argnums(self, plan: _CallPlan) -> tuple[int, ...]:
        """Donate scattered array inputs; never DeviceDatasets (persistent)
        or broadcast state.  Host inputs are freshly staged so donation is
        free; device-resident inputs passed by the caller are consumed
        (``jax.jit`` donate_argnums semantics — see class docstring)."""
        if not self.donate:
            return ()
        return tuple(
            i for i, (spec, is_ds) in enumerate(zip(self.in_specs, plan.dataset_arg))
            if isinstance(spec, Scatter) and not is_ds
        )

    def _sliceable_mask(self, plan: _CallPlan) -> list[bool]:
        # A worker slices the args it scattered (incl. gathered dataset rows).
        return [isinstance(s, Scatter) for s in self.in_specs]

    def _worker_index(self):
        """Combined index along the (possibly nested) data axes."""
        ctx = self.ctx
        idx = jax.lax.axis_index(ctx.data_axes[0])
        for a in ctx.data_axes[1:]:
            idx = idx * ctx.mesh.shape[a] + jax.lax.axis_index(a)
        return idx

    def _take_dataset_rows(self, plan: _CallPlan, dev_args: list, local_idx):
        """Per-worker gather of dataset rows for global ``batch=`` indices."""
        n = self.ctx.n_data
        w = self._worker_index()
        for i, is_ds in enumerate(plan.dataset_arg):
            if not is_ds:
                continue
            L = plan.ds_local_len[i]
            arr = dev_args[i]
            if not plan.routed:
                # aligned: this worker's index chunk lies in its own shard
                rel = local_idx - w * L
                dev_args[i] = jnp.take(arr, rel, axis=0)
            else:
                # routed: every worker sees all B indices; each contributes
                # the rows it owns, a psum assembles the full gathered batch,
                # and the worker keeps its chunk.
                rel = local_idx - w * L
                own = (rel >= 0) & (rel < L)
                rows = jnp.take(arr, jnp.clip(rel, 0, L - 1), axis=0)
                mask = own.reshape(own.shape + (1,) * (rows.ndim - 1))
                rows = jnp.where(mask, rows, jnp.zeros((), rows.dtype))
                rows = jax.lax.psum(rows, self.ctx.data_axes)
                k = local_idx.shape[0] // n
                dev_args[i] = jax.lax.dynamic_slice_in_dim(rows, w * k, k, axis=0)
        return dev_args

    def _build_shard_map(self, plan: _CallPlan, staged, extra) -> Callable:
        ctx = self.ctx
        daxes = ctx.data_axes
        mask = self._sliceable_mask(plan)

        def device_fn(*dev_args):
            dev_args = list(dev_args)
            if plan.indexed and any(plan.dataset_arg):
                local_idx = dev_args[-1]
                dev_args = self._take_dataset_rows(plan, dev_args[:-1], local_idx)
            if plan.num_slices > 1:
                out = sliced_call(
                    self.fn, dev_args, mask, self.out_specs, plan.num_slices,
                    vary_axes=daxes,
                )
            else:
                out = self.fn(*dev_args)
            return self._apply_reduces(out, daxes)

        in_specs = []
        for a, spec in zip(staged, self.in_specs):
            if isinstance(spec, Scatter):
                in_specs.append(jax.tree.map(
                    lambda l: P(daxes, *([None] * (l.ndim - 1))), a))
            else:
                in_specs.append(jax.tree.map(lambda l: P(), a))
        if plan.indexed and any(plan.dataset_arg):
            in_specs.append(P() if plan.routed else P(daxes))

        out_shape = jax.eval_shape(
            lambda *xs: self.fn(*self._probe_args(xs, plan)), *staged, *extra
        )
        out_tree = jax.tree.structure(out_shape)
        op_leaves = _flatten_ops(self.out_specs, out_tree)
        out_pspecs = jax.tree.unflatten(
            out_tree,
            [self._out_pspec(op, daxes) for op in op_leaves],
        )
        # check_vma=False: keep per-worker results LOCAL until the explicit
        # reduce below (paper semantics).  With VMA tracking on, jax.grad of
        # a replicated input inside shard_map auto-inserts a psum (the
        # pbroadcast transpose), silently pre-reducing user gradients.
        mapped = jax.shard_map(
            device_fn, mesh=ctx.mesh, in_specs=tuple(in_specs),
            out_specs=out_pspecs, check_vma=False,
        )
        return jax.jit(mapped, donate_argnums=self._donate_argnums(plan))

    def _probe_args(self, xs, plan: _CallPlan):
        """Build abstract per-worker args for output-structure discovery."""
        ctx = self.ctx
        xs = list(xs)
        if plan.indexed and any(plan.dataset_arg):
            idx = xs[-1]
            xs = xs[:-1]
        out = []
        for a, spec, is_ds in zip(xs, self.in_specs, plan.dataset_arg):
            if isinstance(spec, Scatter):
                def shrink(l):
                    b = l.shape[0] // ctx.n_data
                    if is_ds and plan.indexed:
                        b = idx.shape[0] // ctx.n_data
                    return jnp.zeros((b,) + l.shape[1:], l.dtype)
                out.append(jax.tree.map(shrink, a))
            else:
                out.append(a)
        return out

    @staticmethod
    def _out_pspec(op: Reduce, daxes) -> P:
        if op.op in ("mean", "sum", "max", "min", "last"):
            return P()
        if op.op == "concat":
            return P(daxes)
        return P(daxes)  # None: stacked per-worker results, leading axis

    def _apply_reduces(self, out, daxes):
        leaves, tree = jax.tree.flatten(out)
        op_leaves = _flatten_ops(self.out_specs, tree)
        red = []
        for val, op in zip(leaves, op_leaves):
            if op.op == "mean":
                red.append(jax.lax.pmean(val, daxes))
            elif op.op == "sum":
                red.append(jax.lax.psum(val, daxes))
            elif op.op == "max":
                red.append(jax.lax.pmax(val, daxes))
            elif op.op == "min":
                red.append(jax.lax.pmin(val, daxes))
            elif op.op == "last":
                # identical-by-construction state: return worker 0's copy
                red.append(jax.lax.all_gather(val, daxes, axis=0, tiled=False)[0])
            elif op.op == "concat":
                red.append(val)  # out_spec P(daxes) concatenates shards
            else:  # None: per-worker results stacked on a new leading axis
                red.append(val[None])
        return jax.tree.unflatten(tree, red)

    # ------------------------------------------------------------------
    def _build_gspmd(self, plan: _CallPlan, staged, extra) -> Callable:
        """Beyond-paper backend: fn is the global program; XLA partitions it."""
        ctx = self.ctx
        mask = self._sliceable_mask(plan)

        def global_fn(*g_args):
            g_args = list(g_args)
            if plan.indexed and any(plan.dataset_arg):
                idx = g_args[-1]
                g_args = g_args[:-1]
                for i, is_ds in enumerate(plan.dataset_arg):
                    if is_ds:
                        g_args[i] = jnp.take(g_args[i], idx, axis=0)
            if plan.num_slices > 1:
                return sliced_call(self.fn, g_args, mask, self.out_specs, plan.num_slices)
            return self.fn(*g_args)

        in_sh = []
        for a, spec in zip(staged, self.in_specs):
            if isinstance(spec, Scatter):
                in_sh.append(ctx.sharding(ctx.data_spec(*([None] * (a.ndim - 1)))))
            else:
                in_sh.append(ctx.sharding(P()))
        if plan.indexed and any(plan.dataset_arg):
            in_sh.append(ctx.sharding(ctx.data_spec()))
        return jax.jit(
            global_fn, in_shardings=tuple(in_sh),
            donate_argnums=self._donate_argnums(plan),
        )


def function(
    fn: Callable,
    inputs: Sequence[Any],
    outputs: Any = "mean",
    *,
    ctx: ctx_mod.SynkContext | None = None,
    backend: str = "shard_map",
    name: str | None = None,
    donate: bool = False,
) -> SynkFunction:
    """Paper's ``synk.function`` (replacing ``theano.function``)."""
    return SynkFunction(
        fn, inputs, outputs, ctx=ctx, backend=backend, name=name, donate=donate,
    )


def _pad_indices(idx: np.ndarray, n: int) -> np.ndarray:
    """Pad an index list so it scatters evenly (paper: 'as equal as
    possible' — we repeat trailing indices, cycling when the pad exceeds
    the list; reductions stay approximately correct and ``concat`` outputs
    are sliced back to the original request length)."""
    pad = (-len(idx)) % n
    if not pad:
        return idx
    if len(idx) == 0:
        raise ValueError("batch= may not be empty")
    tail = np.resize(idx[::-1], pad)[::-1]
    return np.concatenate([idx, tail])
