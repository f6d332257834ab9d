"""Flash attention forward — Pallas TPU kernel.

Blocking: grid = (batch, q_heads, Sq / bq).  Each program owns one query
block (bq, d) in VMEM plus the full K/V stream for its KV head (GQA: the
index_map folds q-head -> kv-head).  The inner ``fori_loop`` walks KV
blocks with **dynamic bounds**: causal masking skips blocks above the
diagonal, sliding windows skip blocks below the band — the FLOP savings
the XLA fallback (models/attention.chunked_attention) can only mask.

Online-softmax state (m, l, acc) lives in fp32 VMEM scratch; supports
logit softcap (gemma2) and GQA.  MXU alignment: bq and d should be
multiples of 128 on real TPU (v5e); correctness holds for any size in
interpret mode.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -2.0e38


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, *,
    bq: int, bk: int, sk: int,
    causal: bool, window: int, softcap: float, scale: float,
):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, d)
    d = q.shape[-1]

    q_start = qi * bq
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    nk = sk // bk
    if causal:
        # highest kv block that the last row of this q block can see
        hi = jnp.minimum((q_start + bq - 1) // bk + 1, nk)
    else:
        hi = nk
    if causal and window:
        lo = jnp.maximum((q_start - window + 1) // bk, 0)
    else:
        lo = 0

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                 # (bq, bk)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            ok &= k_pos <= q_pos
        if window:
            ok &= k_pos > q_pos - window
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

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0, 0] = out.astype(o_ref.dtype)


def flash_attention_fwd(
    q, k, v, *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
):
    """q: (B, H, Sq, D); k/v: (B, Hk, Sk, D).  Returns (B, H, Sq, D)."""
    B, H, Sq, D = q.shape
    _, Hk, Sk, _ = k.shape
    rep = H // Hk
    bq = block_q
    while Sq % bq:
        bq //= 2
    bk = block_k
    while Sk % bk:
        bk //= 2
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    kernel = functools.partial(
        _fwd_kernel,
        bq=bq, bk=bk, sk=Sk,
        causal=causal, window=window, softcap=softcap,
        scale=D ** -0.5,
    )
    grid = (B, H, Sq // bq)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, i: (b, h // rep, 0, 0)),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, i: (b, h // rep, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        interpret=interpret,
    )(q, k, v)
