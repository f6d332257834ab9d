"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU).

Per task spec: for each kernel, sweep shapes/dtypes and assert_allclose
against ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.flat_adam.ops import flat_adam_op
from repro.kernels.rmsnorm.ops import rmsnorm_add_op, rmsnorm_op
from repro.kernels.rmsnorm.ref import rmsnorm_add_ref, rmsnorm_ref
from repro.kernels.ssd.ops import ssd_op
from repro.kernels.ssd.ref import ssd_ref
from repro.models.attention import reference_attention
from repro.optim.flat import flat_adam_update


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,H,Hk,D,bq,bk", [
    (128, 4, 2, 32, 32, 32),
    (256, 2, 2, 64, 128, 64),
    (64, 8, 1, 16, 64, 16),     # MQA
])
@pytest.mark.parametrize("kwargs", [
    dict(causal=True),
    dict(causal=False),
    dict(causal=True, window=48),
    dict(causal=True, softcap=30.0),
])
def test_flash_attention_sweep(dtype, S, H, Hk, D, bq, bk, kwargs):
    rng = np.random.default_rng(S + H)
    q = jnp.asarray(rng.normal(size=(2, S, H, D)), dtype)
    k = jnp.asarray(rng.normal(size=(2, S, Hk, D)), dtype)
    v = jnp.asarray(rng.normal(size=(2, S, Hk, D)), dtype)
    out = flash_attention(q, k, v, block_q=bq, block_k=bk, **kwargs)
    ref = attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), **kwargs
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,bq,bk", [(64, 32, 32), (96, 32, 32), (128, 32, 64)])
@pytest.mark.parametrize("H,Hk,D", [
    (2, 2, 32),         # GQA rep 1; the softmax scale multiplies the scores
    (6, 2, 16),         # rep 3; the scale (1/4) is folded into the tiles
])
@pytest.mark.parametrize("window", [0, 40])
def test_flash_attention_grads(dtype, S, bq, bk, H, Hk, D, window):
    """dq, dk, dv of the kernels' backward against jax.grad of the fp32
    reference, on the same inputs, through a random cotangent."""
    rng = np.random.default_rng(S + H + window)
    q = jnp.asarray(rng.normal(size=(2, S, H, D)), dtype)
    k = jnp.asarray(rng.normal(size=(2, S, Hk, D)), dtype)
    v = jnp.asarray(rng.normal(size=(2, S, Hk, D)), dtype)
    w = jnp.asarray(rng.normal(size=(2, S, H, D)), jnp.float32)

    def kernel_loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=window,
                              block_q=bq, block_k=bk)
        return jnp.sum(out.astype(jnp.float32) * w)

    def ref_loss(q, k, v):
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        return jnp.sum(reference_attention(*f32, causal=True, window=window) * w)

    got = jax.grad(kernel_loss, (0, 1, 2))(q, k, v)
    want = jax.grad(ref_loss, (0, 1, 2))(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    for name, g, r in zip("qkv", got, want):
        assert g.dtype == dtype, name
        r = np.asarray(r)
        np.testing.assert_allclose(np.asarray(g, np.float32), r, rtol=tol,
                                   atol=tol * np.abs(r).max(), err_msg=f"d{name}")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows,D,br", [(64, 96, 16), (256, 128, 256), (8, 512, 8)])
def test_rmsnorm_sweep(dtype, rows, D, br):
    rng = np.random.default_rng(rows)
    x = jnp.asarray(rng.normal(size=(rows, D)), dtype)
    g = jnp.asarray(rng.normal(size=(D,)) * 0.1, dtype)
    np.testing.assert_allclose(
        np.asarray(rmsnorm_op(x, g, block_rows=br), np.float32),
        np.asarray(rmsnorm_ref(x, g), np.float32), **_tol(dtype))
    r = jnp.asarray(rng.normal(size=(rows, D)), dtype)
    n1, s1 = rmsnorm_add_op(x, r, g, block_rows=br)
    n2, s2 = rmsnorm_add_ref(x, r, g)
    np.testing.assert_allclose(np.asarray(n1, np.float32),
                               np.asarray(n2, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(s1, np.float32),
                               np.asarray(s2, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("T,H,P,G,N,chunk", [
    (64, 4, 16, 2, 8, 16),
    (128, 2, 8, 1, 16, 32),
    (32, 8, 32, 4, 4, 8),
])
def test_ssd_sweep(dtype, T, H, P, G, N, chunk):
    rng = np.random.default_rng(T + H)
    x = jnp.asarray(rng.normal(size=(2, H, T, P)), dtype)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(2, H, T)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(2, G, T, N)), dtype)
    Cm = jnp.asarray(rng.normal(size=(2, G, T, N)), dtype)
    y = ssd_op(x, dt, A, Bm, Cm, chunk=chunk)
    ref = ssd_ref(x, dt, A, Bm, Cm)
    tol = dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(ref, np.float32), **tol)


@pytest.mark.parametrize("n,block", [(1024, 256), (4096, 4096), (512, 64),
                                     (1000, 256)])   # ragged last tile
@pytest.mark.parametrize("wd", [0.0, 0.01])
def test_flat_adam_sweep(n, block, wd):
    rng = np.random.default_rng(n)
    p = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    m = jnp.asarray(np.abs(rng.normal(size=(n,))) * 0.1, jnp.float32)
    v = jnp.asarray(np.abs(rng.normal(size=(n,))) * 0.1, jnp.float32)
    step = jnp.array([7], jnp.int32)
    p1, m1, v1 = flat_adam_op(p, g, m, v, step, lr=1e-3, weight_decay=wd,
                              block=block)
    p2, m2, v2 = flat_adam_update(p, g, m, v, jnp.int32(7), lr=1e-3)
    if wd:
        p2 = p2 - 1e-3 * wd * p
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-6)
