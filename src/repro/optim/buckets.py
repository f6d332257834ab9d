"""Bucketed gradient-collective engine over the flat buffer (paper §3.3 +
comm/compute overlap).

``optim/flat.py`` gives ONE monolithic flat buffer and therefore ONE giant
all-reduce that serializes the entire communication volume behind the end
of the backward pass.  This module partitions the :class:`FlatLayout` into
fixed-byte **buckets** (default ~4 MiB, boundaries aligned to parameter
boundaries so a tensor never straddles two collectives) and reduces each
bucket independently.  Because the buckets are independent ops in the
lowered program, XLA's latency-hiding scheduler can start reducing early
buckets while later gradient math is still in flight — the same lever
Theano-MPI and ChainerMN identify as the difference between linear and
sub-linear data-parallel scaling.

Two reduction programs, both meant to run *inside* ``shard_map`` over the
data-parallel axes:

* ``bucketed_all_reduce``   — faithful mode: one ``pmean``/``psum`` per
  bucket; every worker ends with the full reduced flat gradient (the
  paper's Appendix-A program, bucketed).
* ``bucketed_reduce_scatter`` / ``bucketed_all_gather`` — ZeRO mode: each
  bucket is reduce-scattered so each worker owns ``1/N`` of it, the fused
  flat-Adam update runs on the owned shard only (sharded optimizer
  state), and the updated parameter shard is all-gathered back.

The scattered layout is *bucket-major*: worker ``w`` owns piece ``w`` of
every bucket, concatenated in bucket order.  Buckets are padded (by at
most ``n_shards - 1`` elements) so each piece is equal-sized; treat
scattered buffers as opaque between ``bucketed_reduce_scatter`` and
``bucketed_all_gather``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .flat import FlatLayout, flat_adam_update

DEFAULT_BUCKET_BYTES = 4 << 20  # ~4 MiB, the NCCL-era sweet spot

# Autotuner target: the fixed per-collective latency may eat at most this
# fraction of each bucket's total collective time.  Smaller fraction ->
# bigger buckets (less overlap granularity), larger -> more launch tax.
_AUTO_LATENCY_FRACTION = 0.05
_AUTO_MIN_BYTES = 1 << 20
_AUTO_MAX_BYTES = 64 << 20


def resolve_bucket_bytes(bucket_mb, *, group_size: int = 1,
                         device_kind: str | None = None) -> int:
    """Resolve ``OptConfig.bucket_mb`` (a float MiB or ``"auto"``) to bytes.

    ``"auto"`` sizes buckets from the roofline model of ``device_kind``
    (``roofline.analysis.PEAKS``; an unknown kind raises): an all-reduce
    over a ring of ``group_size`` workers moves ``2(g-1)/g * b`` wire bytes
    and pays a fixed per-collective latency ``ici_latency_s``; the smallest
    bucket whose wire time keeps that latency under
    ``_AUTO_LATENCY_FRACTION`` of the total maximizes overlap granularity
    without drowning in launch tax.
    """
    if bucket_mb != "auto":
        return int(float(bucket_mb) * (1 << 20))
    from repro.roofline.analysis import peaks_for

    peaks = peaks_for(device_kind)
    bw, lat = peaks.ici_bw, peaks.ici_latency_s
    g = max(int(group_size), 2)   # wire factor of a degenerate group ~ g=2
    wire_factor = 2.0 * (g - 1) / g
    # lat <= f * (lat + wire_factor*b/bw)  =>  b >= lat*(1-f)/f * bw/wire_factor
    f = _AUTO_LATENCY_FRACTION
    b = lat * (1.0 - f) / f * bw / wire_factor
    return int(min(max(b, _AUTO_MIN_BYTES), _AUTO_MAX_BYTES))


@dataclasses.dataclass(frozen=True)
class BucketLayout:
    """A partition of ``[0, total)`` of a FlatLayout into buckets.

    ``starts[i] + sizes[i] == starts[i+1]`` and the buckets cover the
    buffer exactly.  ``padded[i]`` is ``sizes[i]`` rounded up to a multiple
    of ``n_shards`` (used only by the scatter path).
    """

    starts: tuple[int, ...]
    sizes: tuple[int, ...]
    padded: tuple[int, ...]
    n_shards: int
    bucket_bytes: int

    @property
    def num_buckets(self) -> int:
        return len(self.sizes)

    @property
    def total(self) -> int:
        return (self.starts[-1] + self.sizes[-1]) if self.sizes else 0

    @property
    def scattered_total(self) -> int:
        """Global length of a scattered (bucket-major, per-bucket padded)
        buffer: sum of padded bucket sizes."""
        return sum(self.padded)

    @property
    def local_total(self) -> int:
        """Per-worker length of a scattered buffer."""
        return self.scattered_total // self.n_shards


def make_buckets(
    layout: FlatLayout,
    *,
    bucket_bytes: int = DEFAULT_BUCKET_BYTES,
    itemsize: int = 4,
    n_shards: int = 1,
) -> BucketLayout:
    """Greedy partition at parameter boundaries.

    Walks the layout's parameter segments in offset order, closing a bucket
    once it reaches ``bucket_bytes`` worth of elements.  A single parameter
    larger than the target gets a bucket of its own (never split).  The
    alignment tail of the flat buffer (``layout.total - layout.unpadded``)
    rides in the last bucket.
    """
    if bucket_bytes <= 0:
        raise ValueError(f"bucket_bytes must be positive, got {bucket_bytes}")
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    target = max(1, bucket_bytes // itemsize)

    starts: list[int] = []
    sizes: list[int] = []
    acc = 0  # elements accumulated in the open bucket
    for off, size in zip(layout.offsets, layout.sizes):
        if acc == 0:
            starts.append(off)
        acc += size
        if acc >= target:
            sizes.append(acc)
            acc = 0
    if acc:
        sizes.append(acc)
    tail = layout.total - layout.unpadded
    if tail:
        if sizes:
            sizes[-1] += tail
        else:
            starts.append(0)
            sizes.append(layout.total)
    padded = tuple(-(-s // n_shards) * n_shards for s in sizes)
    return BucketLayout(
        starts=tuple(starts), sizes=tuple(sizes), padded=padded,
        n_shards=n_shards, bucket_bytes=bucket_bytes,
    )


def _slices(buf: jnp.ndarray, buckets: BucketLayout):
    return [
        jax.lax.slice_in_dim(buf, s, s + z, axis=0)
        for s, z in zip(buckets.starts, buckets.sizes)
    ]


# ---------------------------------------------------------------------------
# Faithful mode: per-bucket all-reduce
# ---------------------------------------------------------------------------


def bucketed_all_reduce(buf, buckets: BucketLayout, axes, op: str = "mean"):
    """Reduce ``buf`` across ``axes`` one bucket at a time (inside shard_map).

    Numerically identical to a monolithic ``pmean``/``psum`` of the whole
    buffer (same per-element addition order); structurally it emits
    ``num_buckets`` independent collectives that the scheduler can overlap
    with whatever computation still feeds later buckets.
    """
    red = jax.lax.pmean if op == "mean" else jax.lax.psum
    parts = [red(p, axes) for p in _slices(buf, buckets)]
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


# ---------------------------------------------------------------------------
# ZeRO mode: per-bucket reduce-scatter / all-gather
# ---------------------------------------------------------------------------


def bucketed_reduce_scatter(buf, buckets: BucketLayout, axes, op: str = "mean"):
    """Reduce-scatter ``buf`` per bucket: returns the worker's scattered
    shard, length ``buckets.local_total`` (bucket-major layout)."""
    n = buckets.n_shards
    pieces = []
    for part, size, pad_to in zip(_slices(buf, buckets), buckets.sizes, buckets.padded):
        if pad_to != size:
            part = jnp.concatenate([part, jnp.zeros((pad_to - size,), part.dtype)])
        piece = jax.lax.psum_scatter(part, axes, tiled=True)
        if op == "mean":
            piece = piece / n
        pieces.append(piece)
    return jnp.concatenate(pieces) if len(pieces) > 1 else pieces[0]


def bucketed_all_gather(local, buckets: BucketLayout, axes):
    """Inverse of :func:`bucketed_reduce_scatter`'s layout: gather each
    bucket's pieces and reassemble the full flat buffer (length
    ``buckets.total``), dropping the per-bucket padding."""
    n = buckets.n_shards
    parts = []
    off = 0
    for size, pad_to in zip(buckets.sizes, buckets.padded):
        k = pad_to // n
        piece = jax.lax.slice_in_dim(local, off, off + k, axis=0)
        off += k
        full = jax.lax.all_gather(piece, axes, axis=0, tiled=True)
        if pad_to != size:
            full = jax.lax.slice_in_dim(full, 0, size, axis=0)
        parts.append(full)
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def scatter_flat(buf, buckets: BucketLayout, index):
    """Worker ``index``'s scattered shard of a replicated flat buffer —
    what :func:`bucketed_reduce_scatter` would hand that worker if every
    worker contributed ``buf / n`` (used to seed/inspect scattered state).

    ``index`` may be a traced scalar (e.g. ``lax.axis_index``).
    """
    n = buckets.n_shards
    pieces = []
    for start, size, pad_to in zip(buckets.starts, buckets.sizes, buckets.padded):
        k = pad_to // n
        part = jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([
                jax.lax.slice_in_dim(buf, start, start + size, axis=0),
                jnp.zeros((pad_to - size,), buf.dtype),
            ]) if pad_to != size else jax.lax.slice_in_dim(buf, start, start + size, axis=0),
            index * k, k, axis=0,
        )
        pieces.append(part)
    return jnp.concatenate(pieces) if len(pieces) > 1 else pieces[0]


# ---------------------------------------------------------------------------
# Elastic restore: host-side reshard of scattered buffers across dp sizes
# ---------------------------------------------------------------------------
# A checkpointed ZeRO m/v buffer is the GLOBAL scattered array: worker-
# major segments (each ``local_total`` long), every segment bucket-major
# with piece ``w`` of each padded bucket.  That layout bakes in ``(bucket
# boundaries, n_shards)``, so restoring a dp=8 checkpoint onto dp=4 must
# first undo the old scatter and re-apply the new one.  Pure host-numpy
# data movement — bitwise, no arithmetic.


def unscatter_flat(buf, buckets: BucketLayout) -> np.ndarray:
    """Global scattered buffer -> the canonical flat buffer (length
    ``buckets.total``), dropping per-bucket padding."""
    buf = np.asarray(buf)
    if buf.shape != (buckets.scattered_total,):
        raise ValueError(
            f"scattered buffer has shape {buf.shape}, layout wants "
            f"({buckets.scattered_total},)")
    n = buckets.n_shards
    workers = buf.reshape(n, buckets.local_total)
    parts, off = [], 0
    for size, pad_to in zip(buckets.sizes, buckets.padded):
        k = pad_to // n
        # worker-major concat of each worker's piece == the padded bucket
        parts.append(workers[:, off: off + k].reshape(-1)[:size])
        off += k
    return np.concatenate(parts) if parts else buf[:0]


def rescatter_flat(flat, buckets: BucketLayout) -> np.ndarray:
    """Canonical flat buffer -> the global scattered buffer (length
    ``buckets.scattered_total``), zero-filling per-bucket padding —
    the host inverse of :func:`unscatter_flat`."""
    flat = np.asarray(flat)
    if flat.shape != (buckets.total,):
        raise ValueError(
            f"flat buffer has shape {flat.shape}, layout wants "
            f"({buckets.total},)")
    n = buckets.n_shards
    segs: list[list[np.ndarray]] = [[] for _ in range(n)]
    for start, size, pad_to in zip(buckets.starts, buckets.sizes, buckets.padded):
        part = flat[start: start + size]
        if pad_to != size:
            part = np.concatenate(
                [part, np.zeros(pad_to - size, flat.dtype)])
        k = pad_to // n
        for w in range(n):
            segs[w].append(part[w * k: (w + 1) * k])
    if not segs[0]:
        return flat[:0]
    return np.concatenate([np.concatenate(s) for s in segs])


def reshard_scattered(buf, old: BucketLayout, new: BucketLayout) -> np.ndarray:
    """Re-lay a scattered buffer saved under ``old`` (its dp size and
    bucket boundaries) for a job running under ``new``.  Adam's moment
    padding lanes are identically zero (their gradient is always the
    scatter pad), so dropping and re-zero-filling them is bitwise."""
    if old.total != new.total:
        raise ValueError(
            f"bucket layouts cover different flat buffers: "
            f"{old.total} vs {new.total} elements")
    return rescatter_flat(unscatter_flat(buf, old), new)


# ---------------------------------------------------------------------------
# Fused flat-Adam dispatch (Pallas kernel on TPU, jnp reference elsewhere)
# ---------------------------------------------------------------------------


def flat_adam_apply(p, g, m, v, step, *, lr, beta1, beta2, eps,
                    weight_decay: float = 0.0, use_kernel: bool | None = None):
    """One fused elementwise Adam pass over flat fp32 buffers.

    ``use_kernel=None`` picks the Pallas ``kernels/flat_adam`` kernel on
    TPU and the pure-jnp reference elsewhere (the kernel's interpret mode
    is correct but slow off-TPU).  Returns ``(p', m', v')``.
    """
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel:
        from repro.kernels.flat_adam.kernel import flat_adam

        return flat_adam(
            p, g, m, v, step,
            lr=lr, beta1=beta1, beta2=beta2, eps=eps, weight_decay=weight_decay,
        )
    p_new, m_new, v_new = flat_adam_update(
        p, g, m, v, step, lr=lr, beta1=beta1, beta2=beta2, eps=eps
    )
    if weight_decay:
        p_new = p_new - lr * weight_decay * p
    return p_new, m_new, v_new
