"""Fused flat-buffer Adam — Pallas TPU kernel.

The paper (§3.3) flattens all gradients into one array so the all-reduce
is a single collective; this kernel is the natural conclusion: the
optimizer update is ONE fused elementwise pass over the flat fp32
buffers (p, g, m, v -> p', m', v'), instead of one kernel launch and
3x read + 3x write per parameter tensor.  Grid over 1-D tiles.

The bias corrections ``1 - beta**t`` are computed by XLA outside the
kernel (Mosaic has no lowering for ``pow`` with a traced exponent) and
ride into it as two SMEM scalars.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# A rank-1 f32 block is laid out in (8, 128) vregs: keep it a multiple of
# 1024 elements.  256 KiB per buffer, 7 buffers double-buffered ~3.5 MiB
# of VMEM.
BLOCK = 65536


def _adam_kernel(corr_ref, p_ref, g_ref, m_ref, v_ref,
                 p_out, m_out, v_out, *,
                 lr: float, beta1: float, beta2: float, eps: float,
                 weight_decay: float):
    p = p_ref[...]
    g = g_ref[...]
    m = beta1 * m_ref[...] + (1.0 - beta1) * g
    v = beta2 * v_ref[...] + (1.0 - beta2) * g * g
    mhat = m / corr_ref[0]
    vhat = v / corr_ref[1]
    upd = lr * mhat / (jnp.sqrt(vhat) + eps)
    if weight_decay:
        upd = upd + lr * weight_decay * p
    p_out[...] = p - upd
    m_out[...] = m
    v_out[...] = v


def flat_adam(p, g, m, v, step, *,
              lr: float, beta1: float = 0.9, beta2: float = 0.95,
              eps: float = 1e-8, weight_decay: float = 0.0,
              block: int = BLOCK, interpret: bool | None = None):
    """All buffers: (n,) fp32.  step: 1-based int step count, () or (1,).

    The grid is ``cdiv(n, block)``: a length that is not a multiple of the
    block gets a ragged last tile (its out-of-range lanes are never
    written) instead of a smaller block.  Returns (p', m', v').
    """
    n = p.shape[0]
    block = min(block, n)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t = jnp.reshape(step, ()).astype(jnp.float32)
    corr = jnp.stack([1.0 - beta1 ** t, 1.0 - beta2 ** t])
    kernel = functools.partial(
        _adam_kernel, lr=lr, beta1=beta1, beta2=beta2, eps=eps,
        weight_decay=weight_decay,
    )
    vec = pl.BlockSpec((block,), lambda i: (i,))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, block),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), vec, vec, vec, vec],
        out_specs=[vec, vec, vec],
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.float32)] * 3,
        # p', m', v' overwrite p, m, v: three fewer buffer-sized temps
        # (~1.6 GiB at smollm-360m's flat length)
        input_output_aliases={1: 0, 3: 1, 4: 2},
        interpret=interpret,
        name="flat_adam",
    )(corr, p, g, m, v)
