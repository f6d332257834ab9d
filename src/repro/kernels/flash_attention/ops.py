"""Jitted public wrapper for the flash attention kernels.

Accepts the model layout (B, S, H, D) used across models/, transposes to
the kernel layout (B, H, S, D), and dispatches to the Pallas kernels.

Differentiable through a ``custom_vjp`` that is flash attention's own
backward: the forward kernel also returns the rows' log-sum-exp, the
residuals are ``q, k, v, out, lse`` (never a score or probability
tensor), ``delta = rowsum(dO * out)`` is formed once, and one Pallas
kernel produces ``dq``, ``dk`` and ``dv``, skipping fully masked blocks as
the forward does.  Blocks are 512 rows where the length allows
(``kernel.BLOCK``), halved until they divide it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import (
    BLOCK, LANES, VMEM_LIMIT, flash_attention_bwd, flash_attention_fwd,
    resident_bytes,
)


def flash_applies(q_shape, k_shape, dtype, *, causal: bool,
                  softcap: float = 0.0, q_offset=0, kv_len=None) -> bool:
    """Whether the kernels serve this attention (model layout shapes): a
    TPU backend; causal self-attention from position 0 over every key
    (``Sq == Sk``, no ``q_offset``, no ``kv_len``); no logit softcap; a
    length that tiles into 128-row blocks; and whole streams that fit the
    kernels' VMEM with room for their blocks."""
    (_, sq, h, d), (_, sk, hk, _) = q_shape, k_shape
    return (jax.default_backend() == "tpu" and causal and not softcap
            and sq == sk and sq % LANES == 0
            and isinstance(q_offset, int) and q_offset == 0 and kv_len is None
            and resident_bytes(sq, sk, d, h // hk, jnp.dtype(dtype).itemsize)
            <= VMEM_LIMIT * 3 // 4)


def _to_kernel(x):
    return x.transpose(0, 2, 1, 3)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, window, softcap, block_q, block_k, interpret):
    out = flash_attention_fwd(
        _to_kernel(q), _to_kernel(k), _to_kernel(v),
        causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return _to_kernel(out)


def _flash_fwd(q, k, v, causal, window, softcap, block_q, block_k, interpret):
    qt, kt, vt = _to_kernel(q), _to_kernel(k), _to_kernel(v)
    out, lse = flash_attention_fwd(
        qt, kt, vt, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, return_lse=True,
        interpret=interpret,
    )
    return _to_kernel(out), (qt, kt, vt, out, lse)


def _flash_bwd(causal, window, softcap, block_q, block_k, interpret,
               residuals, g):
    qt, kt, vt, out, lse = residuals
    do = _to_kernel(g)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, :, None, :]                 # (B, H, 1, S)
    dqt, dk, dv = flash_attention_bwd(
        qt, kt, vt, do, lse, delta,
        causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return dqt.transpose(0, 3, 1, 2), _to_kernel(dk), _to_kernel(dv)


_flash.defvjp(_flash_fwd, _flash_bwd)


@partial(jax.jit, static_argnames=("causal", "window", "softcap", "block_q",
                                   "block_k", "interpret"))
def flash_attention(
    q, k, v, *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = BLOCK,
    block_k: int = BLOCK,
    interpret: bool | None = None,
):
    """q: (B, Sq, H, D); k/v: (B, Sk, Hk, D) — model layout."""
    return _flash(q, k, v, causal, window, softcap, block_q, block_k, interpret)
