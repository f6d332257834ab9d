"""Paged decode attention — Pallas TPU kernel.

One decode step against the block-table KV cache.  Grid = (B, Hk): each
program owns one lane's queries for one KV head — q (rep, D) — plus the
full pool stream for that head, the lane's block-table row, and its
length.  The inner ``fori_loop`` walks **only the mapped blocks the lane
can attend** (``length // bs + 1`` of them — dynamic bound), resolving
each logical block to its physical pool row through the table and
maintaining online-softmax state (m, l, acc) in fp32, so the gathered
(B, max_len) lane view the jnp reference materialises never exists.

Like the flash kernel, the pool rides in VMEM via BlockSpec (fine in
interpret mode and for smoke pools; a production TPU deployment would
keep the pool in HBM and DMA blocks — noted in docs/serving.md).  MXU
alignment wants rep*D and bs*D in 128-multiples on real hardware;
correctness holds for any size in interpret mode, which is what CI
validates against the oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -2.0e38


def _paged_kernel(
    len_ref, tab_ref, q_ref, k_ref, v_ref, o_ref, *,
    bs: int, nb: int, window: int, softcap: float, scale: float,
):
    q = q_ref[0, 0].astype(jnp.float32) * scale
    rep, d = q.shape
    length = len_ref[0, 0]

    # blocks this lane attends: positions [0, length] -> length//bs + 1
    hi = jnp.minimum(length // bs + 1, nb)

    def body(j, carry):
        m, l, acc = carry
        blk = tab_ref[0, j]
        k = k_ref[blk, :, 0, :].astype(jnp.float32)          # (bs, d)
        v = v_ref[blk, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                    # (rep, bs)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (rep, bs), 1)
        ok = pos <= length
        if window:
            ok &= pos > length - window
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((rep,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((rep,), jnp.float32)
    a0 = jnp.zeros((rep, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, hi, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0, 0] = out.astype(o_ref.dtype)


def paged_attention_fwd(
    q, k_pool, v_pool, lengths, tables, *,
    window: int = 0,
    softcap: float = 0.0,
    interpret: bool | None = None,
):
    """q: (B, Hk, rep, D); pools: (NB, bs, Hk, D); lengths: (B,) int32;
    tables: (B, nb) int32.  Returns (B, Hk, rep, D)."""
    B, Hk, rep, D = q.shape
    NB, bs = k_pool.shape[:2]
    nb = tables.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    kernel = functools.partial(
        _paged_kernel,
        bs=bs, nb=nb, window=window, softcap=softcap, scale=D ** -0.5,
    )
    grid = (B, Hk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, h: (b, 0)),           # lengths (B, 1)
            pl.BlockSpec((1, nb), lambda b, h: (b, 0)),          # tables
            pl.BlockSpec((1, 1, rep, D), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((NB, bs, 1, D), lambda b, h: (0, 0, h, 0)),
            pl.BlockSpec((NB, bs, 1, D), lambda b, h: (0, 0, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, D), lambda b, h: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hk, rep, D), q.dtype),
        interpret=interpret,
    )(lengths[:, None], tables, q, k_pool, v_pool)
