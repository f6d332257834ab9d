"""Flash attention — Pallas TPU kernels: the forward and the backward.

Layout (B, H, S, D) for q and (B, Hk, S, D) for k/v; GQA maps query head
``h`` to KV head ``h // rep``.  Two kernels, each named by its
``pallas_call`` (a trace finds them by the instruction name):

- ``flash_attention_fwd``: grid (B, H, Sq / bq).  One query block in
  VMEM against the KV head's whole K/V stream; online softmax (m, l, acc)
  in fp32.  Optionally writes the rows' log-sum-exp ``lse`` (fp32), the
  only softmax statistic the backward needs.
- ``flash_attention_bwd``: grid (B, Hk).  One KV head with its ``rep``
  query heads: for each KV block, the query blocks that can see it
  recompute ``p = exp(s - lse)`` and give ``dk``, ``dv`` (accumulated per
  KV block) and ``dq`` (accumulated for the whole head in VMEM, in the
  transposed layout (d, Sq), returned as (B, H, D, Sq)).  It works on the
  transposed scores (bk, bq), so ``lse`` and ``delta`` broadcast as rows.
  One pass gives all three gradients: ``p`` and ``ds`` are computed once.

The loops over blocks have **dynamic bounds**: causal masking skips
blocks above the diagonal, a sliding window skips those below its band,
and only blocks the diagonal or the band's edge cuts pay for the mask.
No score or probability tensor leaves VMEM.  MXU operands stay in the
input dtype with fp32 accumulation; ``lse``, ``delta`` and the
accumulators are fp32.  ``lse`` and ``delta = rowsum(dO * O)`` are kept
as (B, H, 1, S) rows.  A softmax scale that is a power of two (head
size 64 or 256) is applied to the q (forward) or k (backward) tile, where
it is exact; any other scale multiplies the scores.  Logit softcap
(gemma2) is supported.  On a TPU, bq, bk and Sq must be multiples of 128;
interpret mode takes any size.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38
LANES = 128
BLOCK = 512                           # rows per block, before _fit
_NT = (((1,), (1,)), ((), ()))       # a @ b.T
_NN = (((1,), (0,)), ((), ()))       # a @ b
_TN = (((0,), (0,)), ((), ()))       # a.T @ b
# scoped VMEM each kernel may use (a v5e core has 128 MiB)
VMEM_LIMIT = 32 * 2**20


def resident_bytes(sq: int, sk: int, d: int, rep: int, itemsize: int) -> int:
    """VMEM the backward holds for one KV head, double-buffered with lanes
    padded to 128: its ``rep`` query heads' q and dO, their lse and delta
    rows, dq (out and fp32 accumulator), and K, V, dK, dV.  The forward
    holds less."""
    lanes = -(-d // LANES) * LANES
    q = 2 * 2 * rep * sq * (lanes * itemsize + 8 * 4)
    dq = rep * d * sq * (2 * itemsize + 4)
    kv = 4 * 2 * sk * lanes * itemsize
    return q + dq + kv


def _params(interpret: bool):
    return None if interpret else pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT)


def _interpret(interpret):
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _fit(n: int, block: int) -> int:
    while n % block:
        block //= 2
    return block


def _scales(d: int) -> tuple[float, float]:
    """(tile scale, score scale) whose product is d**-0.5: the whole of it
    on the tile where that is exact (a power of two), else on the scores."""
    scale = d ** -0.5
    return (scale, 1.0) if math.frexp(scale)[0] == 0.5 else (1.0, scale)


def _spans(start, size: int, block: int, n: int, causal: bool, window: int,
           *, rows_are_queries: bool):
    """Block ranges of the stream a tile meets: ``(lo, full_lo, full_hi,
    hi)``.  Blocks in [lo, hi) hold some visible entry; those in
    [full_lo, full_hi) hold no masked one.

    ``rows_are_queries``: the tile is the query rows [start, start+size)
    and the stream is keys (forward); else the tile is keys and the stream
    is query rows (backward)."""
    if not causal:
        return (0, 0, 0, n) if window else (0, 0, n, n)
    if rows_are_queries:
        hi = jnp.minimum((start + size - 1) // block + 1, n)
        full_hi = (start + 1) // block
        if window:
            lo = jnp.maximum(start - window + 1, 0) // block
            full_lo = jnp.maximum(start + size - window + block - 1, 0) // block
        else:
            lo = full_lo = 0
    else:
        lo = start // block
        full_lo = (start + size + block - 2) // block
        if window:
            hi = jnp.minimum((start + size + window - 2) // block + 1, n)
            full_hi = (start + window) // block
        else:
            hi = full_hi = n
    full_lo = jnp.clip(full_lo, lo, hi)
    full_hi = jnp.clip(full_hi, full_lo, hi)
    return lo, full_lo, full_hi, hi


def _masked_loops(spans, body, carry):
    """Run ``body(masked)`` over a tile's spans, in block order."""
    lo, full_lo, full_hi, hi = spans
    carry = jax.lax.fori_loop(lo, full_lo, body(True), carry)
    carry = jax.lax.fori_loop(full_lo, full_hi, body(False), carry)
    return jax.lax.fori_loop(full_hi, hi, body(True), carry)


def _visible(q_pos, k_pos, causal: bool, window: int):
    ok = jnp.ones(q_pos.shape, jnp.bool_)
    if causal:
        ok &= k_pos <= q_pos
    if window:
        ok &= k_pos > q_pos - window
    return ok


def _logits(a, b, scale: float, softcap: float):
    """``a @ b.T`` in fp32, times ``scale``, capped; and the cap's
    ``tanh`` (None without a cap) for the backward."""
    s = jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)
    if scale != 1.0:
        s = s * scale
    if not softcap:
        return s, None
    t = jnp.tanh(s / softcap)
    return softcap * t, t


def _as_row(col):
    """(n, 1) -> (1, n), through one lane-aligned transpose."""
    return jnp.broadcast_to(col, (col.shape[0], LANES)).T[:1]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *lse_ref,
                bq: int, bk: int, sk: int, causal: bool, window: int,
                softcap: float, tile_scale: float, score_scale: float):
    q0 = pl.program_id(2) * bq
    q = q_ref[0, 0]                                      # (bq, d)
    if tile_scale != 1.0:
        q = q * jnp.asarray(tile_scale, q.dtype)
    d = q.shape[-1]
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    def body(masked):
        def step(j, carry):
            m, l, acc = carry
            k = k_ref[0, 0, pl.ds(j * bk, bk), :]
            v = v_ref[0, 0, pl.ds(j * bk, bk), :]
            s, _ = _logits(q, k, score_scale, softcap)  # (bq, bk)
            if masked:
                k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
                s = jnp.where(_visible(q_pos, k_pos, causal, window), s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new
        return step

    carry = (jnp.full((bq, 1), NEG_INF, jnp.float32),
             jnp.zeros((bq, 1), jnp.float32),
             jnp.zeros((bq, d), jnp.float32))
    spans = _spans(q0, bq, bk, sk // bk, causal, window, rows_are_queries=True)
    m, l, acc = _masked_loops(spans, body, carry)
    l = jnp.maximum(l, 1e-30)
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    if lse_ref:
        lse_ref[0][0, 0] = _as_row(m + jnp.log(l))


def flash_attention_fwd(
    q, k, v, *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = BLOCK,
    block_k: int = BLOCK,
    return_lse: bool = False,
    interpret: bool | None = None,
):
    """q: (B, H, Sq, D); k/v: (B, Hk, Sk, D).  Returns (B, H, Sq, D), and
    with ``return_lse`` also the rows' log-sum-exp, fp32 (B, H, 1, Sq)."""
    B, H, Sq, D = q.shape
    _, Hk, Sk, _ = k.shape
    rep = H // Hk
    bq, bk = _fit(Sq, block_q), _fit(Sk, block_k)
    tile_scale, score_scale = _scales(D)
    kernel = functools.partial(
        _fwd_kernel, bq=bq, bk=bk, sk=Sk, causal=causal, window=window,
        softcap=softcap, tile_scale=tile_scale, score_scale=score_scale)
    out_specs = [pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype)]
    if return_lse:
        out_specs.append(pl.BlockSpec((1, 1, 1, bq), lambda b, h, i: (b, h, 0, i)))
        out_shape.append(jax.ShapeDtypeStruct((B, H, 1, Sq), jnp.float32))
    interpret = _interpret(interpret)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, Sq // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, i: (b, h // rep, 0, 0)),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, i: (b, h // rep, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=_params(interpret),
        name="flash_attention_fwd",
    )(q, k, v)
    return tuple(out) if return_lse else out[0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dqt_ref, dk_ref, dv_ref, dqt_acc, *,
                bq: int, bk: int, sq: int, sk: int, rep: int,
                causal: bool, window: int, softcap: float,
                tile_scale: float, score_scale: float):
    dqt_acc[...] = jnp.zeros(dqt_acc.shape, jnp.float32)

    def kv_step(j, _):
        k0 = j * bk
        kv_rows = pl.ds(pl.multiple_of(k0, bk), bk)
        k = k_ref[0, 0, kv_rows, :]                      # (bk, d)
        if tile_scale != 1.0:
            k = k * jnp.asarray(tile_scale, k.dtype)
        v = v_ref[0, 0, kv_rows, :]
        k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
        spans = _spans(k0, bk, bq, sq // bq, causal, window,
                       rows_are_queries=False)
        carry = (jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32))
        for r in range(rep):
            def body(masked, r=r):
                def step(i, carry):
                    dk, dv = carry
                    rows = pl.ds(pl.multiple_of(i * bq, bq), bq)
                    q = q_ref[0, r, rows, :]             # (bq, d)
                    do = do_ref[0, r, rows, :]
                    lse = lse_ref[0, r, :, rows]         # (1, bq)
                    delta = delta_ref[0, r, :, rows]
                    s, t = _logits(k, q, score_scale, softcap)  # (bk, bq)
                    if masked:
                        q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
                        s = jnp.where(_visible(q_pos, k_pos, causal, window), s, NEG_INF)
                    p = jnp.exp(s - lse)
                    dv = dv + jax.lax.dot_general(
                        p.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
                    dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
                    ds = p * (dp - delta)
                    if softcap:
                        ds = ds * (1.0 - t * t)
                    ds = ds.astype(q.dtype)
                    dk = dk + jax.lax.dot_general(
                        ds, q, _NN, preferred_element_type=jnp.float32)
                    dqt_acc[r, :, rows] += jax.lax.dot_general(
                        k, ds, _TN, preferred_element_type=jnp.float32)  # (d, bq)
                    return dk, dv
                return step
            carry = _masked_loops(spans, body, carry)
        dk, dv = carry
        dk_ref[0, 0, kv_rows, :] = (dk * (tile_scale * score_scale)).astype(dk_ref.dtype)
        dv_ref[0, 0, kv_rows, :] = dv.astype(dv_ref.dtype)
        return 0

    jax.lax.fori_loop(0, sk // bk, kv_step, 0)
    dqt = dqt_acc[...]
    if score_scale != 1.0:
        dqt = dqt * score_scale
    dqt_ref[0] = dqt.astype(dqt_ref.dtype)


def flash_attention_bwd(
    q, k, v, do, lse, delta, *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = BLOCK,
    block_k: int = BLOCK,
    interpret: bool | None = None,
):
    """Gradients of flash attention.  q/do: (B, H, Sq, D); k/v: (B, Hk,
    Sk, D); lse (the forward's) and delta = rowsum(do * out): fp32
    (B, H, 1, Sq).  Returns dq transposed, (B, H, D, Sq), and dk, dv as
    (B, Hk, Sk, D), in the dtypes of q, k, v."""
    B, H, Sq, D = q.shape
    _, Hk, Sk, _ = k.shape
    rep = H // Hk
    bq, bk = _fit(Sq, block_q), _fit(Sk, block_k)
    tile_scale, score_scale = _scales(D)
    interpret = _interpret(interpret)
    q_all = pl.BlockSpec((1, rep, Sq, D), lambda b, g: (b, g, 0, 0))
    rows_all = pl.BlockSpec((1, rep, 1, Sq), lambda b, g: (b, g, 0, 0))
    kv_all = pl.BlockSpec((1, 1, Sk, D), lambda b, g: (b, g, 0, 0))
    dqt_all = pl.BlockSpec((1, rep, D, Sq), lambda b, g: (b, g, 0, 0))
    return pl.pallas_call(
        functools.partial(
            _bwd_kernel, bq=bq, bk=bk, sq=Sq, sk=Sk, rep=rep, causal=causal,
            window=window, softcap=softcap, tile_scale=tile_scale,
            score_scale=score_scale),
        grid=(B, Hk),
        in_specs=[q_all, kv_all, kv_all, q_all, rows_all, rows_all],
        out_specs=[dqt_all, kv_all, kv_all],
        out_shape=[jax.ShapeDtypeStruct((B, H, D, Sq), q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((rep, D, Sq), jnp.float32)],
        interpret=interpret,
        compiler_params=_params(interpret),
        name="flash_attention_bwd",
    )(q, k, v, do, lse, delta)
