"""The comparisons that decide ``correct``, kept with the benchmark.

Trees here are in a reference's layout: top-level arrays, and under
``"layers"`` arrays stacked on a leading layer axis.  A *leaf* is one
top-level array or one layer's slice of a stacked array.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def leaf_norms(tree: dict):
    """Float32 L2 norm of every leaf: the top-level arrays by name, then
    each stacked array layer by layer, by name (traceable)."""
    parts = [jnp.sqrt(jnp.sum(jnp.square(tree[k].astype(jnp.float32))))[None]
             for k in sorted(tree) if k != "layers"]
    for k in sorted(tree.get("layers", {})):
        x = tree["layers"][k].astype(jnp.float32)
        parts.append(jnp.sqrt(jnp.sum(jnp.square(x),
                                      axis=tuple(range(1, x.ndim)))))
    return jnp.concatenate(parts)


def worst_leaf_gap(got, ref, keep=None) -> tuple[float, int]:
    """max over leaves of |got - ref| / max(ref, median leaf of ref): the gap
    between two norms, against the reference's norm of that leaf or of the
    median leaf, whichever is larger.  Returns (gap, leaf index)."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    idx = np.arange(ref.size) if keep is None else np.flatnonzero(keep)
    floor = np.median(ref[idx])
    gaps = np.abs(got[idx] - ref[idx]) / np.maximum(ref[idx], floor)
    j = int(np.argmax(gaps))
    return float(gaps[j]), int(idx[j])


def moving_leaves(ref_grad_norms, share: float = 1e-3):
    """Leaves whose reference gradient is above ``share`` of the median
    leaf's; the others move under Adam by round-off alone."""
    g = np.asarray(ref_grad_norms, np.float64)
    return g >= share * np.median(g)


def loss_gap(got, ref) -> float:
    """Largest relative gap between two sequences of losses."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref) / np.abs(ref)))


def served_logit_gaps(logits, tokens):
    """For each position, how far the served token's logit lies below the
    best logit there.  logits (S, V) float32, tokens (S,) int."""
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return best - got


def control_logit_gaps(ref_logits, low_logits):
    """The gap, in the reference's logits, of the token that a lower
    precision puts first, at every position."""
    pick = jnp.argmax(low_logits, axis=-1)
    return served_logit_gaps(ref_logits, pick)

