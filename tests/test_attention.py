"""Chunked attention vs naive oracle; distributed decode correctness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models.attention import (
    DecodeSharding, chunked_attention, decode_attention,
    decode_attention_pos_minor, pick_chunk, reference_attention, rope,
)


def _mk(B, S, H, Hk, D, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(dtype))
    k = jnp.asarray(rng.normal(size=(B, S, Hk, D)).astype(dtype))
    v = jnp.asarray(rng.normal(size=(B, S, Hk, D)).astype(dtype))
    return q, k, v


@pytest.mark.parametrize("kwargs", [
    dict(causal=True),
    dict(causal=False),
    dict(causal=True, window=24),
    dict(causal=True, window=16),
    dict(causal=True, softcap=20.0),
    dict(causal=True, window=16, softcap=30.0),
    dict(causal=True, kv_len=40),
])
def test_chunked_matches_reference(kwargs):
    q, k, v = _mk(2, 64, 8, 2, 16)
    out = chunked_attention(q, k, v, q_chunk=16, kv_chunk=16, **kwargs)
    ref = reference_attention(q, k, v, **kwargs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


@settings(max_examples=20, deadline=None)
@given(
    S=st.sampled_from([32, 48, 64]),
    chunk=st.sampled_from([8, 16, 64]),
    rep=st.sampled_from([1, 2, 4]),
    window=st.sampled_from([0, 8, 24]),
)
def test_chunked_property_sweep(S, chunk, rep, window):
    Hk = 2
    q, k, v = _mk(1, S, Hk * rep, Hk, 8, seed=S + chunk)
    out = chunked_attention(q, k, v, q_chunk=chunk, kv_chunk=chunk,
                            causal=True, window=window)
    ref = reference_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-5, rtol=5e-5)


def test_pick_chunk():
    assert pick_chunk(1500, 256) == 250
    assert pick_chunk(4096, 256) == 256
    assert pick_chunk(7, 256) == 7
    assert pick_chunk(13, 4) == 1


def test_rope_preserves_norm_and_relativity():
    B, S, H, D = 2, 16, 2, 8
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    xr = rope(x, pos)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(xr), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-4)
    # relative property: <rope(q,m), rope(k,n)> depends only on m-n
    q = jnp.asarray(rng.normal(size=(1, 1, 1, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 1, 1, D)).astype(np.float32))
    def dot(m, n):
        qm = rope(q, jnp.full((1, 1), m))
        kn = rope(k, jnp.full((1, 1), n))
        return float(jnp.sum(qm * kn))
    np.testing.assert_allclose(dot(3, 1), dot(7, 5), rtol=1e-4)


@pytest.mark.parametrize("window", [0, 6])
def test_decode_matches_reference_chain(mesh, window):
    """Run 6 decode steps; each must match the naive attention over the
    prefix (the distributed flash-decode LSE combine is exact)."""
    B, Hk, rep, D, Smax = 2, 2, 3, 8, 16
    H = Hk * rep
    rng = np.random.default_rng(1)
    ks = jnp.asarray(rng.normal(size=(B, Smax, Hk, D)).astype(np.float32))
    vs = jnp.asarray(rng.normal(size=(B, Smax, Hk, D)).astype(np.float32))
    qs = jnp.asarray(rng.normal(size=(B, Smax, H, D)).astype(np.float32))
    sh = DecodeSharding.choose(mesh, B)
    kc = jnp.zeros((B, Smax, Hk, D), jnp.float32)
    vc = jnp.zeros_like(kc)
    for t in range(6):
        q = qs[:, t].reshape(B, Hk, rep, D)
        out, kc, vc = decode_attention(
            q, kc, vc, ks[:, t], vs[:, t], jnp.int32(t),
            sharding=sh, window=window,
        )
        ref = reference_attention(
            qs[:, t:t + 1], ks[:, :t + 1], vs[:, :t + 1],
            causal=True, window=window, q_offset=t,
        )
        np.testing.assert_allclose(
            np.asarray(out).reshape(B, 1, H, D), np.asarray(ref),
            atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("window,softcap", [(0, 0.0), (5, 0.0), (0, 20.0),
                                            (5, 20.0)])
@pytest.mark.parametrize("vector", [True, False])
def test_pos_minor_decode_matches_decode_attention(mesh, window, softcap,
                                                    vector):
    """The position-minor step attends and writes what ``decode_attention``
    does on the same cache in (B, S, Hk, D) order: lanes at 0, mid and
    the last position, garbage beyond each length, window and softcap."""
    B, Hk, rep, D, S = 4, 2, 3, 8, 16
    rng = np.random.default_rng(7)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape).astype(np.float32))
    q, kc, vc = f(B, Hk, rep, D), f(B, S, Hk, D), f(B, S, Hk, D)
    kn, vn = f(B, Hk, D), f(B, Hk, D)
    idx = jnp.asarray([0, 6, S - 1, 11], jnp.int32) if vector \
        else jnp.int32(S - 1)
    kw = dict(sharding=DecodeSharding.choose(mesh, B), window=window,
              softcap=softcap)
    want, wk, wv = jax.jit(lambda *a: decode_attention(*a, **kw))(
        q, kc, vc, kn, vn, idx)
    pm = lambda c: jnp.transpose(c, (0, 2, 3, 1))          # (B, Hk, D, S)
    got, gk, gv = jax.jit(lambda *a: decode_attention_pos_minor(*a, **kw))(
        q, pm(kc), pm(vc), kn, vn, idx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-6, rtol=3e-6)
    np.testing.assert_array_equal(np.asarray(gk), np.asarray(pm(wk)))
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(pm(wv)))
