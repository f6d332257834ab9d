"""Mamba2 SSD chunk scan — Pallas TPU kernel.

Grid = (B, H): each program owns one (batch, head) stream.  The SSM state
(N x P) lives in fp32 VMEM scratch and is carried across chunks by an
in-kernel ``fori_loop``; each chunk step is three MXU matmuls (C B^T
scores, intra-chunk combine, state inject) — the paper's GPU kernel is a
fused recurrent scan; on TPU the chunked matmul decomposition is the
MXU-native adaptation (DESIGN.md §2).

B/C group tensors are indexed per head via the BlockSpec index_map
(h -> h // heads_per_group): no (B,T,H,N) expansion is materialised.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, o_ref, state_ref, *,
                chunk: int, seq: int):
    h = pl.program_id(1)
    P = x_ref.shape[-1]
    N = b_ref.shape[-1]
    nc = seq // chunk
    A = a_ref[0]                                         # scalar decay rate

    state_ref[...] = jnp.zeros_like(state_ref)

    def chunk_step(ci, _):
        sl = pl.ds(ci * chunk, chunk)
        x = x_ref[0, 0, sl, :].astype(jnp.float32)       # (Q,P)
        dt = dt_ref[0, 0, sl].astype(jnp.float32)        # (Q,)
        Bm = b_ref[0, 0, sl, :].astype(jnp.float32)      # (Q,N)
        Cm = c_ref[0, 0, sl, :].astype(jnp.float32)

        la = dt * A                                      # (Q,) log decay
        cum = jnp.cumsum(la)                             # inclusive
        seg = cum[-1]
        xdt = x * dt[:, None]

        # intra-chunk: scores[q,k] = C_q.B_k * exp(cum_q - cum_k), k <= q
        scores = jax.lax.dot_general(
            Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                                # (Q,Q)
        decay = jnp.exp(cum[:, None] - cum[None, :])
        mask = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
            jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        scores = jnp.where(mask, scores * decay, 0.0)
        y = jax.lax.dot_general(
            scores, xdt, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )                                                # (Q,P)

        # inter-chunk: y += (C * exp(cum)) @ S_prev
        S = state_ref[...]
        y = y + jax.lax.dot_general(
            Cm * jnp.exp(cum)[:, None], S, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        # state update: S = exp(seg) * S + sum_k exp(seg - cum_k) B_k xdt_k^T
        w = jnp.exp(seg - cum)
        S_new = S * jnp.exp(seg) + jax.lax.dot_general(
            Bm * w[:, None], xdt, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        state_ref[...] = S_new
        o_ref[0, 0, sl, :] = y.astype(o_ref.dtype)
        return ()

    jax.lax.fori_loop(0, nc, chunk_step, ())


def ssd_fwd(x, dt, A, Bm, Cm, *, chunk: int = 128, interpret: bool | None = None):
    """x: (B, H, T, P); dt: (B, H, T); A: (H,); Bm/Cm: (B, G, T, N).

    Returns y (B, H, T, P).  T must be divisible by chunk.
    """
    B, H, T, P = x.shape
    G, N = Bm.shape[1], Bm.shape[-1]
    rep = H // G
    while T % chunk:
        chunk //= 2
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kernel = functools.partial(_ssd_kernel, chunk=chunk, seq=T)
    return pl.pallas_call(
        kernel,
        grid=(B, H),
        in_specs=[
            pl.BlockSpec((1, 1, T, P), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, T), lambda b, h: (b, h, 0)),
            pl.BlockSpec((1,), lambda b, h: (h,)),
            pl.BlockSpec((1, 1, T, N), lambda b, h: (b, h // rep, 0, 0)),
            pl.BlockSpec((1, 1, T, N), lambda b, h: (b, h // rep, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, T, P), lambda b, h: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(x, dt, A, Bm, Cm)
