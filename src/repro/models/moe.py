"""Expert-parallel MoE FFN (Qwen3-style: 128 experts, top-8, softmax-gated).

Layout: experts are sharded over the ``model`` (tp) mesh axis; tokens of a
data-parallel column are sequence-sharded over the same axis between blocks
(sequence parallelism).  The layer:

  1. all-gathers the column's tokens over ``model`` (each rank sees the
     full column),
  2. routes locally (top-k), computes capacity slots with a sort-based
     position-in-expert (no (T,E,C) one-hot — that tensor is intractable
     at production sizes),
  3. gathers tokens into a per-local-expert (E_loc, C, D) buffer, runs the
     expert FFNs as batched matmuls (MXU-shaped),
  4. scatter-adds weighted outputs back to token slots and
     reduce-scatters the result over ``model``, restoring the
     sequence-parallel layout.

The collective pattern (all-gather + reduce-scatter over tp) matches what
tensor parallelism would pay for a dense FFN of the same width, so expert
parallelism here adds no extra collective classes — this is one of the
beyond-paper design choices recorded in DESIGN.md.

FSDP (``rules.fsdp``): expert weights arrive sharded on d_model and are
all-gathered per layer inside the shard_map.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ArchConfig
from .common import ShardRules


def expert_capacity(n_tokens: int, cfg: ArchConfig) -> int:
    moe = cfg.moe
    c = int(np.ceil(n_tokens * moe.top_k / moe.num_experts * moe.capacity_factor))
    c = max(c, min(n_tokens * moe.top_k, 8))   # decode-sized floors
    return int(np.ceil(c / 8) * 8)             # lane-aligned


def moe_ffn(
    x, router_w, w_gate, w_up, w_down, *,
    cfg: ArchConfig,
    mesh: Mesh,
    rules: ShardRules,
):
    """x: (B, S, D) global. Returns (out (B, S, D), aux metrics dict)."""
    E = cfg.moe.num_experts
    K = cfg.moe.top_k
    D = cfg.d_model
    tp = rules.tp
    tp_size = mesh.shape[tp] if tp else 1
    dp = tuple(a for a in rules.dp if a in mesh.axis_names)

    B, S, _ = x.shape
    seq_sharded = tp is not None and S % tp_size == 0 and S >= tp_size
    ndp = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    n_tokens_col = max(B // max(ndp, 1), 1) * S
    C = expert_capacity(n_tokens_col, cfg)
    E_loc = E // tp_size if tp else E

    fsdp = rules.fsdp if rules.fsdp and rules.fsdp in mesh.axis_names else None

    def shard_fn(x_loc, rw, wg, wu, wd):
        # x_loc: (B_l, S_l, D); expert weights local (E_loc, D[/fsdp], F)
        if fsdp:
            wg = jax.lax.all_gather(wg, fsdp, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp, axis=2, tiled=True)
        if seq_sharded and tp:
            x_col = jax.lax.all_gather(x_loc, tp, axis=1, tiled=True)  # (B_l, S, D)
        else:
            x_col = x_loc
        Bl = x_col.shape[0]
        T = Bl * x_col.shape[1]
        xt = x_col.reshape(T, D)

        # --- routing (computed redundantly on every tp rank; negligible) ---
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), rw.astype(jnp.float32))
        topk_w, topk_i = jax.lax.top_k(logits, K)          # (T, K)
        topk_w = jax.nn.softmax(topk_w, axis=-1)           # Qwen3 renormalises

        flat_e = topk_i.reshape(-1)                        # (T*K,)
        flat_w = topk_w.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)

        # --- sort-based position-in-expert (static shapes, O(TK log TK)) ---
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        starts = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
        ranks_sorted = jnp.arange(flat_e.shape[0], dtype=jnp.int32) - starts[sorted_e].astype(jnp.int32)
        pos = jnp.zeros_like(flat_e).at[order].set(ranks_sorted)

        # --- local-expert slot assignment ---
        e_off = (jax.lax.axis_index(tp) * E_loc) if tp else 0
        e_loc = flat_e - e_off
        keep = (pos < C) & (e_loc >= 0) & (e_loc < E_loc)
        e_write = jnp.where(keep, e_loc, E_loc)            # OOB row -> dropped
        pos_c = jnp.clip(pos, 0, C - 1)

        idx_buf = jnp.full((E_loc + 1, C), T, jnp.int32)   # sentinel T -> zero row
        idx_buf = idx_buf.at[e_write, pos_c].set(flat_t, mode="drop")
        w_buf = jnp.zeros((E_loc + 1, C), jnp.float32)
        w_buf = w_buf.at[e_write, pos_c].set(flat_w, mode="drop")
        idx_buf, w_buf = idx_buf[:E_loc], w_buf[:E_loc]

        # --- expert compute: (E_loc, C, D) batched matmuls ---
        x_pad = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)], axis=0)
        xs = x_pad[idx_buf]                                # (E_loc, C, D)
        g = jnp.einsum("ecd,edf->ecf", xs, wg.astype(xs.dtype))
        u = jnp.einsum("ecd,edf->ecf", xs, wu.astype(xs.dtype))
        h = jax.nn.silu(g) * u
        y = jnp.einsum("ecf,efd->ecd", h, wd.astype(xs.dtype))
        y = y * w_buf[..., None].astype(y.dtype)

        # --- combine: scatter-add back to token slots ---
        out_col = jnp.zeros((T + 1, D), y.dtype)
        out_col = out_col.at[idx_buf.reshape(-1)].add(y.reshape(-1, D), mode="drop")
        out_col = out_col[:T].reshape(Bl, -1, D)

        if tp:
            if seq_sharded:
                out = jax.lax.psum_scatter(out_col, tp, scatter_dimension=1, tiled=True)
            else:
                out = jax.lax.psum(out_col, tp)
        else:
            out = out_col

        # --- load-balance aux (Switch-style: E * sum_e f_e * p_e) ---
        probs = jax.nn.softmax(logits, axis=-1)
        frac = jnp.mean(
            (jax.nn.one_hot(topk_i[:, 0], E, dtype=jnp.float32)), axis=0
        )
        lb = E * jnp.sum(frac * jnp.mean(probs, axis=0))
        dropped = 1.0 - jnp.mean(keep.astype(jnp.float32))
        return out, lb, dropped

    seq_spec = tp if seq_sharded else None
    in_specs = (
        P(dp or None, seq_spec, None),                 # x
        P(),                                           # router
        P(tp, fsdp, None),                             # w_gate (E, D, F)
        P(tp, fsdp, None),                             # w_up
        P(tp, None, fsdp),                             # w_down (E, F, D)
    )
    out_specs = (P(dp or None, seq_spec, None), P(), P())
    fn = jax.shard_map(
        shard_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    out, lb, dropped = fn(x, router_w, w_gate, w_up, w_down)
    return out.astype(x.dtype), {"lb_loss": lb, "drop_frac": dropped}


def moe_ffn_reference(x, router_w, w_gate, w_up, w_down, *, cfg: ArchConfig):
    """Dense oracle: every expert computed for every token, no capacity.

    Used by tests; differs from moe_ffn only via capacity drops (tests use
    a capacity factor that guarantees no drops).
    """
    E, K = cfg.moe.num_experts, cfg.moe.top_k
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router_w.astype(jnp.float32))
    topk_w, topk_i = jax.lax.top_k(logits, K)
    topk_w = jax.nn.softmax(topk_w, axis=-1)
    weights = jnp.zeros((xt.shape[0], E), jnp.float32)
    weights = weights.at[jnp.arange(xt.shape[0])[:, None], topk_i].set(topk_w)
    g = jnp.einsum("td,edf->tef", xt, w_gate.astype(xt.dtype))
    u = jnp.einsum("td,edf->tef", xt, w_up.astype(xt.dtype))
    h = jax.nn.silu(g) * u
    y = jnp.einsum("tef,efd->ted", h, w_down.astype(xt.dtype))
    out = jnp.einsum("ted,te->td", y.astype(jnp.float32), weights)
    return out.reshape(B, S, D).astype(x.dtype)
