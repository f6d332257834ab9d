"""Attention: RoPE, chunked (flash-style) attention, distributed decode.

``chunked_attention`` is the portable XLA path: an online-softmax scan over
query/key chunks so the (S x S) score matrix is never materialised — the
same blocking the Pallas kernel (kernels/flash_attention) uses on TPU, and
the oracle it is tested against.

``decode_attention`` is the serving path: KV caches are sharded along the
*sequence* axis across the ``model`` (and, for batch-1 long-context, also
the ``data``/``pod``) mesh axes; each shard computes a partial softmax and
the results are combined with a log-sum-exp reduction (distributed
flash-decoding).  This is what makes 32k/500k-token caches fit.
``decode_attention_pos_minor`` is the same step over a cache stored with
the position axis minor-most, the slotted layout of the lm and hybrid
families.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x, positions, theta: float = 10_000.0):
    """Rotary embedding.  x: (..., S, H, D); positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) * (np.log(theta) / half))
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., :, None, :]   # (..., S, 1, half)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Chunked attention (training / prefill)
# ---------------------------------------------------------------------------

def pick_chunk(n: int, target: int) -> int:
    """Largest divisor of ``n`` that is <= target (attention chunk size)."""
    c = min(target, n)
    while n % c:
        c -= 1
    return max(c, 1)


def _mask_scores(s, pos_q, pos_k, causal, window, kv_len):
    """s: (..., Q, K) fp32; pos_q: (Q,), pos_k: (K,)."""
    ok = jnp.ones((pos_q.shape[0], pos_k.shape[0]), bool)
    if causal:
        ok &= pos_k[None, :] <= pos_q[:, None]
    if window:
        ok &= pos_k[None, :] > pos_q[:, None] - window
    if kv_len is not None:
        ok &= pos_k[None, :] < kv_len
    return jnp.where(ok, s, NEG_INF)


def chunked_attention(
    q, k, v, *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_chunk: int = 256,
    kv_chunk: int = 256,
    q_offset: int = 0,
    kv_len=None,
):
    """Memory-bounded attention.

    q: (B, Sq, H, D); k/v: (B, Sk, Hk, D) with H % Hk == 0 (GQA).
    Sliding-window causal attention uses a *static band* of KV chunks
    (exact, no wasted blocks); full attention scans all KV chunks with
    masking (the Pallas kernel skips masked blocks on TPU).
    Returns (B, Sq, H, D) in q.dtype.
    """
    B, Sq, H, D = q.shape
    _, Sk, Hk, _ = k.shape
    rep = H // Hk
    scale = D ** -0.5

    q_chunk = pick_chunk(Sq, q_chunk)
    kv_chunk = pick_chunk(Sk, kv_chunk)
    nq, nk = Sq // q_chunk, Sk // kv_chunk

    qg = q.reshape(B, nq, q_chunk, Hk, rep, D).transpose(1, 0, 3, 4, 2, 5)
    # qg: (nq, B, Hk, rep, qc, D)
    kg = k.reshape(B, nk, kv_chunk, Hk, D).transpose(1, 0, 3, 2, 4)   # (nk,B,Hk,kc,D)
    vg = v.reshape(B, nk, kv_chunk, Hk, D).transpose(1, 0, 3, 2, 4)

    # the static band assumes q and kv blocks are aligned from position 0;
    # a (possibly traced) nonzero q_offset — chunked prefill resuming at a
    # mid-prompt position — falls back to the masked full scan
    aligned = isinstance(q_offset, (int, np.integer)) and q_offset == 0
    band = causal and window and window < Sk and q_chunk == kv_chunk and aligned
    # q-chunk rows [iC, iC+C-1] may attend keys in [iC - window + 1, iC + C - 1]
    # -> ceil((window + C - 1) / C) KV chunks ending at chunk i.
    nb = int(np.ceil((window + kv_chunk - 1) / kv_chunk)) if band else nk

    def q_step(_, inputs):
        qi, i = inputs
        pos_q = q_offset + i * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, jn):
            m, l, acc = carry
            if band:
                off = jn
                j = jnp.maximum(i - off, 0)
                valid_chunk = (i - off) >= 0
            else:
                j = jn
                valid_chunk = True
            kj = jax.lax.dynamic_index_in_dim(kg, j, axis=0, keepdims=False)
            vj = jax.lax.dynamic_index_in_dim(vg, j, axis=0, keepdims=False)
            pos_k = j * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum(
                "bhrqd,bhkd->bhrqk", qi.astype(jnp.float32), kj.astype(jnp.float32)
            ) * scale
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            s = _mask_scores(s, pos_q, pos_k, causal, window, kv_len)
            if band:
                s = jnp.where(valid_chunk, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhrqk,bhkd->bhrqd", p, vj.astype(jnp.float32)
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hk, rep, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hk, rep, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, Hk, rep, q_chunk, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), jnp.arange(nb)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        # cast per-chunk: the stacked output (and any SPMD reshard of it)
        # stays in the compute dtype rather than f32
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, (qg, jnp.arange(nq)))
    # outs: (nq, B, Hk, rep, qc, D) -> (B, Sq, H, D)
    return outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, H, D)


def reference_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                        q_offset: int = 0, kv_len=None):
    """Naive O(S^2)-memory oracle for tests."""
    B, Sq, H, D = q.shape
    _, Sk, Hk, _ = k.shape
    rep = H // Hk
    qf = q.astype(jnp.float32).reshape(B, Sq, Hk, rep, D)
    s = jnp.einsum("bqhrd,bkhd->bhrqk", qf, k.astype(jnp.float32)) * (D ** -0.5)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    pos_q = q_offset + jnp.arange(Sq)
    pos_k = jnp.arange(Sk)
    s = _mask_scores(s, pos_q, pos_k, causal, window, kv_len)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Distributed decode (sequence-sharded KV cache, LSE combine)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DecodeSharding:
    """How the KV cache is laid out on the mesh for decoding."""

    mesh: Mesh
    batch_axes: tuple[str, ...]     # axes sharding the batch dim (may be empty)
    seq_axes: tuple[str, ...]       # axes sharding the cache sequence dim

    @classmethod
    def choose(cls, mesh: Mesh, batch: int) -> "DecodeSharding":
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        tp = tuple(a for a in ("model",) if a in mesh.axis_names)
        ndp = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
        if batch % max(ndp, 1) == 0 and batch >= ndp:
            return cls(mesh, dp, tp)
        # batch too small (long-context, batch=1): spread the sequence over
        # every axis instead.
        return cls(mesh, (), dp + tp)

    def cache_spec(self) -> P:
        b = self.batch_axes or None
        s = self.seq_axes or None
        return P(b, s, None, None)     # (B, S, Hk, D)


def decode_attention(
    q, k_cache, v_cache, k_new, v_new, cur_index, *,
    sharding: DecodeSharding,
    window: int = 0,
    softcap: float = 0.0,
):
    """One decoding step against a sequence-sharded KV cache.

    q:            (B, Hk, rep, D) — current-token queries (RoPE applied)
    k_cache/v_cache: (B, S, Hk, D) — sharded per ``sharding.cache_spec()``
    k_new/v_new:  (B, Hk, D) — current token's K/V, written at ``cur_index``
    cur_index:    number of tokens already in the cache — scalar int32
                  (all sequences aligned, the classic batched-decode path)
                  or a ``(B,)`` vector (continuous batching: each slot is
                  at its own position; writes and validity masks are
                  per-row)

    Returns (out (B, Hk, rep, D), k_cache', v_cache').
    """
    mesh = sharding.mesh
    baxes, saxes = sharding.batch_axes, sharding.seq_axes
    S = k_cache.shape[1]
    n_seq = int(np.prod([mesh.shape[a] for a in saxes])) if saxes else 1
    s_loc = S // n_seq
    vec_index = jnp.ndim(cur_index) == 1

    def shard_fn(q, kc, vc, kn, vn, idx):
        # local shapes: q (Bl, Hk, rep, D); kc/vc (Bl, s_loc, Hk, D)
        if saxes:
            shard_id = jax.lax.axis_index(saxes)
        else:
            shard_id = jnp.int32(0)
        start = shard_id * s_loc
        pos = start + jnp.arange(s_loc)

        if vec_index:
            # per-slot positions: per-row scatter writes + per-row valid
            # masks.  The scatter touches only the Bl written rows — a
            # one-hot select would rewrite the whole (Bl, s_loc, Hk, D)
            # cache (the dominant decode tensor) every step.
            rel = idx - start                              # (Bl,)
            in_range = (rel >= 0) & (rel < s_loc)
            rows = jnp.arange(rel.shape[0])
            safe = jnp.clip(rel, 0, s_loc - 1)

            def write(c, new):
                keep = c[rows, safe]                       # (Bl, Hk, D)
                val = jnp.where(
                    in_range[:, None, None], new.astype(c.dtype), keep)
                return c.at[rows, safe].set(val)

            valid = pos[None, :] <= idx[:, None]           # (Bl, s_loc)
            if window:
                valid &= pos[None, :] > idx[:, None] - window
            vmask = valid[:, None, None, :]
        else:
            local_pos = jnp.clip(idx - start, 0, s_loc - 1)
            in_range = (idx >= start) & (idx < start + s_loc)

            def write(c, new):
                upd = jax.lax.dynamic_update_slice_in_dim(
                    c, new[:, None].astype(c.dtype), local_pos, axis=1
                )
                return jnp.where(in_range, upd, c)

            valid = pos <= idx
            if window:
                valid &= pos > idx - window
            vmask = valid[None, None, None, :]

        kc = write(kc, kn)
        vc = write(vc, vn)

        s = jnp.einsum(
            "bhrd,bshd->bhrs", q.astype(jnp.float32), kc.astype(jnp.float32)
        ) * (q.shape[-1] ** -0.5)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(vmask, s, NEG_INF)

        m_loc = jnp.max(s, axis=-1)
        p = jnp.exp(s - m_loc[..., None])
        l_loc = jnp.sum(p, axis=-1)
        o_loc = jnp.einsum("bhrs,bshd->bhrd", p, vc.astype(jnp.float32))

        if saxes:
            m_g = jax.lax.pmax(m_loc, saxes)
            m_g = jnp.maximum(m_g, -1e30)
            corr = jnp.exp(m_loc - m_g)
            l_g = jax.lax.psum(l_loc * corr, saxes)
            o_g = jax.lax.psum(o_loc * corr[..., None], saxes)
        else:
            l_g, o_g = l_loc, o_loc
        out = (o_g / jnp.maximum(l_g, 1e-30)[..., None]).astype(q.dtype)
        return out, kc, vc

    b = baxes or None
    s_sp = saxes or None
    in_specs = (
        P(b, None, None, None),          # q
        P(b, s_sp, None, None),          # k_cache
        P(b, s_sp, None, None),          # v_cache
        P(b, None, None),                # k_new
        P(b, None, None),                # v_new
        P(b) if vec_index else P(),      # cur_index (vector is per-slot)
    )
    out_specs = (
        P(b, None, None, None),
        P(b, s_sp, None, None),
        P(b, s_sp, None, None),
    )
    fn = jax.shard_map(
        shard_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, k_new, v_new, cur_index)


def decode_attention_pos_minor(
    q, k_cache, v_cache, k_new, v_new, cur_index, *,
    sharding: DecodeSharding,
    window: int = 0,
    softcap: float = 0.0,
):
    """One decoding step against one layer of a position-minor KV cache —
    the slotted serve cache of the lm and hybrid families.

    q:               (B, Hk, rep, D) — current-token queries (RoPE applied)
    k_cache/v_cache: (B, Hk, D, S) — the position axis minor-most,
                     sharded over ``sharding.seq_axes``
    k_new/v_new:     (B, Hk, D) — current token's K/V
    cur_index:       scalar or ``(B,)`` — tokens already in each lane

    The TPU tiles the two minor dims of an array; with the position axis
    minor-most a (Hk, D, S) lane is stored unpadded and both dots read it
    where it lies.  (In (S, Hk, D) order the (Hk, D) = (5, 64) tile pads
    3.2x and XLA re-lays the whole layer out around every step.)

    The dots attend the cached positions ``< cur_index``; the new token's
    own score and value are merged by the log-sum-exp rule — the same
    softmax over positions ``<= cur_index``, in fp32, as
    :func:`decode_attention`.  So the dots do not wait on the token
    write, a masked select that XLA fuses into the caller's in-place
    ``dynamic_update_slice`` of the layer into its donated stack.

    Returns (out (B, Hk, rep, D), k_cache', v_cache').
    """
    mesh = sharding.mesh
    baxes, saxes = sharding.batch_axes, sharding.seq_axes
    B, S = q.shape[0], k_cache.shape[-1]
    n_seq = int(np.prod([mesh.shape[a] for a in saxes])) if saxes else 1
    s_loc = S // n_seq
    idx = jnp.broadcast_to(jnp.asarray(cur_index, jnp.int32), (B,))
    scale = q.shape[-1] ** -0.5

    def scores(q, k):
        s = jnp.einsum("bhrd,bhd...->bhr...", q, k) * scale
        return softcap * jnp.tanh(s / softcap) if softcap else s

    def shard_fn(q, kc, vc, kn, vn, idx):
        # local shapes: q (Bl, Hk, rep, D); kc/vc (Bl, Hk, D, s_loc)
        start = jax.lax.axis_index(saxes) * s_loc if saxes else 0
        pos = start + jnp.arange(s_loc)
        valid = pos[None, :] < idx[:, None]                # (Bl, s_loc)
        if window:
            valid &= pos[None, :] > idx[:, None] - window
        qf = q.astype(jnp.float32)
        s = jnp.where(valid[:, None, None, :],
                      scores(qf, kc.astype(jnp.float32)), NEG_INF)
        # a lane with no cached position in range keeps m finite, so its
        # masked scores give p = 0 and the new token alone carries it
        m = jnp.maximum(jnp.max(s, axis=-1), -1e30)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bhrs,bhds->bhrd", p, vc.astype(jnp.float32))
        if saxes:
            m_g = jax.lax.pmax(m, saxes)
            corr = jnp.exp(m - m_g)
            l = jax.lax.psum(l * corr, saxes)
            o = jax.lax.psum(o * corr[..., None], saxes)
            m = m_g

        # the new token, rounded to the cache's dtype as it is stored
        kn, vn = kn.astype(kc.dtype), vn.astype(vc.dtype)
        s_new = scores(qf, kn.astype(jnp.float32))         # (Bl, Hk, rep)
        m_all = jnp.maximum(m, s_new)
        a, b = jnp.exp(m - m_all), jnp.exp(s_new - m_all)
        o = o * a[..., None] + b[..., None] * vn.astype(jnp.float32)[:, :, None]
        out = (o / (l * a + b)[..., None]).astype(q.dtype)

        at = (pos[None, :] == idx[:, None])[:, None, None, :]
        kc = jnp.where(at, kn[..., None], kc)
        vc = jnp.where(at, vn[..., None], vc)
        return out, kc, vc

    b = baxes or None
    s_sp = saxes or None
    kv_spec = P(b, None, None, s_sp)
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(b, None, None, None), kv_spec, kv_spec,
                  P(b, None, None), P(b, None, None), P(b)),
        out_specs=(P(b, None, None, None), kv_spec, kv_spec),
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, k_new, v_new, idx)


# ---------------------------------------------------------------------------
# Paged KV cache: block-table plumbing + paged decode
# ---------------------------------------------------------------------------
#
# The pool layout is (num_blocks, block_size, Hk, D): logical position ``p``
# of a lane lives at physical row ``table[p // bs] * bs + p % bs`` of the
# flattened pool.  Physical block 0 is a write sink (serve/paged.py reserves
# it): unmapped table entries and invalid positions route writes there, so
# garbage never lands in a live block and the sink is never read (reads are
# masked to ``pos <= length``, and every readable position's block is
# mapped by construction).


def paged_gather(pool, tables):
    """Materialise lanes from the pool in logical position order.

    pool: (NB, bs, ...); tables: (B, nb) int32.  Returns (B, nb*bs, ...)
    — index ``p`` of a row is logical position ``p`` of that lane
    (garbage from the null block where unmapped; callers mask by length).
    """
    g = jnp.take(pool, tables, axis=0)                  # (B, nb, bs, ...)
    return g.reshape(tables.shape[0], -1, *pool.shape[2:])


def _physical_rows(table, positions, bs: int, nb: int):
    """Flat pool rows for logical ``positions`` under one table row; out-of
    -range positions clamp into the last block (callers only pass them for
    stale lanes whose table rows are nulled — the clamp lands in the sink)."""
    li = jnp.clip(positions // bs, 0, nb - 1)
    blk = jnp.take(table, li)
    off = jnp.clip(positions - li * bs, 0, bs - 1)
    return blk * bs + off


def paged_write_token(pool, tables, lengths, new):
    """Write one new token's K or V per lane at logical ``lengths[b]``.

    pool: (NB, bs, Hk, D); tables: (B, nb); new: (B, Hk, D).  Lanes whose
    block for that position is unmapped (free/stale lanes) write into the
    null sink.  Only the B written rows are touched — the paged analogue
    of ``decode_attention``'s per-row scatter.
    """
    NB, bs = pool.shape[:2]
    nb = tables.shape[1]
    B = tables.shape[0]
    li = jnp.clip(lengths // bs, 0, nb - 1)
    blk = jnp.take_along_axis(tables, li[:, None], axis=1)[:, 0]
    off = jnp.clip(lengths - li * bs, 0, bs - 1)
    flat = pool.reshape(NB * bs, *pool.shape[2:])
    flat = flat.at[blk * bs + off].set(new.astype(pool.dtype))
    return flat.reshape(pool.shape)


def paged_write_positions(pool, table, positions, new, valid=None):
    """Scatter a chunk of positions of ONE lane into the pool.

    pool: (NB, bs, Hk, D) or layer-stacked (Lf, NB, bs, Hk, D);
    table: (nb,) int32; positions: (P,); new matches pool's lead plus
    (P, Hk, D).  ``valid=False`` positions (prompt padding) divert to the
    null sink.
    """
    stacked = pool.ndim == 5
    NB, bs = (pool.shape[1], pool.shape[2]) if stacked else pool.shape[:2]
    rows = _physical_rows(table, positions, bs, table.shape[0])
    if valid is not None:
        rows = jnp.where(valid, rows, 0)
    if stacked:
        flat = pool.reshape(pool.shape[0], NB * bs, *pool.shape[3:])
        flat = flat.at[:, rows].set(new.astype(pool.dtype))
    else:
        flat = pool.reshape(NB * bs, *pool.shape[2:])
        flat = flat.at[rows].set(new.astype(pool.dtype))
    return flat.reshape(pool.shape)


def paged_decode_attention(
    q, k_pool, v_pool, k_new, v_new, lengths, tables, *,
    window: int = 0,
    softcap: float = 0.0,
    impl: str = "ref",
):
    """One decoding step against the paged (block-table) KV cache.

    q:             (B, Hk, rep, D) — current-token queries (RoPE applied)
    k_pool/v_pool: (NB, bs, Hk, D) — the shared block pool
    k_new/v_new:   (B, Hk, D) — written at logical position ``lengths[b]``
    lengths:       (B,) int32 — tokens already in each lane
    tables:        (B, nb) int32 — the lanes' block-table rows
    impl:          "ref" gathers lanes and runs the masked-softmax XLA
                   path (bitwise-identical to
                   ``decode_attention`` on equal inputs); "pallas"
                   dispatches the block-walking kernel
                   (kernels/paged_attention) that never materialises the
                   gathered lanes.

    Returns (out (B, Hk, rep, D), k_pool', v_pool').
    """
    k_pool = paged_write_token(k_pool, tables, lengths, k_new)
    v_pool = paged_write_token(v_pool, tables, lengths, v_new)
    if impl == "pallas":
        from repro.kernels import paged_attention
        out = paged_attention(
            q, k_pool, v_pool, lengths, tables,
            window=window, softcap=softcap,
        )
    else:
        # the kernel's jnp oracle IS the production reference path, so the
        # kernel-vs-ref tests cover exactly what serves here
        from repro.kernels.paged_attention.ref import paged_attention_ref
        out = paged_attention_ref(
            q, k_pool, v_pool, lengths, tables,
            window=window, softcap=softcap,
        )
    return out, k_pool, v_pool
