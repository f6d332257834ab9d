"""Mamba2 (SSD) blocks — chunked, matmul-based state-space scan.

The SSD ("state-space duality") form computes the selective-SSM with
chunk-local attention-like matmuls plus an inter-chunk state recurrence:
MXU-friendly on TPU (the Pallas kernel kernels/ssd mirrors this blocking).

Shapes follow Mamba2: x (B,T,H,P); dt (B,T,H); A (H,) negative;
B/C (B,T,G,N) with H % G == 0.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from .common import ParamSpec, ShardRules, constrain, rms_norm


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------


def ssd_chunked(x, dt, A, Bm, Cm, *, chunk: int, return_state: bool = False):
    """Returns y (B,T,H,P) (and the final SSM state if requested)."""
    Bsz, T, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    Q = min(chunk, T)
    T_real = T
    if T % Q:
        # pad with dt=0 steps: decay=exp(0)=1 and input weight dt=0, so the
        # padded tail is an identity on the state and the outputs slice off
        pad = Q - T % Q
        zpad = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        x, dt, Bm, Cm = zpad(x), zpad(dt), zpad(Bm), zpad(Cm)
        T = T + pad
    nc = T // Q

    x = x.astype(jnp.float32)
    dt = dt.astype(jnp.float32)
    A = A.astype(jnp.float32)
    Bh = jnp.repeat(Bm.astype(jnp.float32), rep, axis=2)    # (B,T,H,N)
    Ch = jnp.repeat(Cm.astype(jnp.float32), rep, axis=2)

    xc = x.reshape(Bsz, nc, Q, H, P)
    dtc = dt.reshape(Bsz, nc, Q, H)
    Bc = Bh.reshape(Bsz, nc, Q, H, N)
    Cc = Ch.reshape(Bsz, nc, Q, H, N)

    la = dtc * A                                # (B,nc,Q,H) log-decay <= 0
    cum = jnp.cumsum(la, axis=2)                # inclusive within chunk
    seg_total = cum[:, :, -1]                   # (B,nc,H)

    xdt = xc * dtc[..., None]                   # dt-weighted inputs

    # --- intra-chunk: Y[q] += sum_{k<=q} exp(cum[q]-cum[k]) C_q.B_k x_k ---
    scores = jnp.einsum("bcqhn,bckhn->bchqk", Cc, Bc)
    decay = jnp.exp(
        cum.transpose(0, 1, 3, 2)[..., :, None] - cum.transpose(0, 1, 3, 2)[..., None, :]
    )                                            # (B,nc,H,Q,K)
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    scores = jnp.where(mask, scores * decay, 0.0)
    y_intra = jnp.einsum("bchqk,bckhp->bcqhp", scores, xdt)

    # --- chunk states: S_c = sum_k exp(seg_total - cum[k]) B_k (x_k)^T ---
    w_state = jnp.exp(seg_total[:, :, None, :] - cum)        # (B,nc,Q,H)
    states = jnp.einsum("bckhn,bckhp->bchnp", Bc * w_state[..., None], xdt)

    # --- inter-chunk recurrence over chunk index ---
    def step(S, inp):
        st, g = inp                              # st: (B,H,N,P), g: (B,H)
        S_new = S * jnp.exp(g)[..., None, None] + st
        return S_new, S                          # emit state BEFORE this chunk

    S0 = jnp.zeros((Bsz, H, N, P), jnp.float32)
    S_final, S_prev = jax.lax.scan(
        step, S0,
        (states.transpose(1, 0, 2, 3, 4), seg_total.transpose(1, 0, 2)),
    )
    S_prev = S_prev.transpose(1, 0, 2, 3, 4)     # (B,nc,H,N,P)

    # --- inter contribution: Y[q] += exp(cum[q]) C_q . S_prev ---
    y_inter = jnp.einsum(
        "bcqhn,bchnp->bcqhp", Cc * jnp.exp(cum)[..., None], S_prev
    )
    y = (y_intra + y_inter).reshape(Bsz, T, H, P)[:, :T_real]
    if return_state:
        return y, S_final
    return y


def ssd_reference(x, dt, A, Bm, Cm):
    """Step-by-step recurrence oracle (tests)."""
    Bsz, T, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    Bh = jnp.repeat(Bm.astype(jnp.float32), rep, axis=2)
    Ch = jnp.repeat(Cm.astype(jnp.float32), rep, axis=2)
    dt = dt.astype(jnp.float32)
    xf = x.astype(jnp.float32)

    def step(S, inp):
        xt, dtt, bt, ct = inp                    # (B,H,P),(B,H),(B,H,N),(B,H,N)
        decay = jnp.exp(dtt * A)                 # (B,H)
        S = S * decay[..., None, None] + jnp.einsum(
            "bhn,bhp->bhnp", bt, xt * dtt[..., None]
        )
        y = jnp.einsum("bhn,bhnp->bhp", ct, S)
        return S, y

    S0 = jnp.zeros((Bsz, H, N, P), jnp.float32)
    _, ys = jax.lax.scan(
        step, S0,
        (xf.transpose(1, 0, 2, 3), dt.transpose(1, 0, 2),
         Bh.transpose(1, 0, 2, 3), Ch.transpose(1, 0, 2, 3)),
    )
    return ys.transpose(1, 0, 2, 3)


def ssd_decode_step(S, x, dt, A, Bm, Cm):
    """One-token state update.  S: (B,H,N,P); x: (B,H,P); dt: (B,H);
    Bm/Cm: (B,G,N).  Returns (S', y (B,H,P))."""
    H = x.shape[1]
    rep = H // Bm.shape[1]
    Bh = jnp.repeat(Bm.astype(jnp.float32), rep, axis=1)
    Ch = jnp.repeat(Cm.astype(jnp.float32), rep, axis=1)
    decay = jnp.exp(dt.astype(jnp.float32) * A.astype(jnp.float32))
    S = S * decay[..., None, None] + jnp.einsum(
        "bhn,bhp->bhnp", Bh, x.astype(jnp.float32) * dt[..., None]
    )
    y = jnp.einsum("bhn,bhnp->bhp", Ch, S)
    return S, y


# ---------------------------------------------------------------------------
# Mamba2 block
# ---------------------------------------------------------------------------


def mamba_dims(cfg: ArchConfig):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    H = d_inner // s.head_dim
    conv_ch = d_inner + 2 * s.n_groups * s.state
    return d_inner, H, conv_ch


def mamba_block_specs(cfg: ArchConfig, n_layers: int) -> dict:
    """Stacked (n_layers, ...) Mamba2 block parameters."""
    D = cfg.d_model
    s = cfg.ssm
    d_inner, H, conv_ch = mamba_dims(cfg)
    L = (n_layers,)
    ll = (None,)
    dt = jnp.dtype(cfg.param_dtype)
    d_proj = 2 * d_inner + 2 * s.n_groups * s.state + H
    return {
        "ln": ParamSpec(L + (D,), ll + (None,), dt, init_scale=0.0),
        "in_proj": ParamSpec(L + (D, d_proj), ll + ("fsdp", "tp"), dt),
        "conv_w": ParamSpec(L + (s.conv_kernel, conv_ch), ll + (None, "tp"), dt),
        "conv_b": ParamSpec(L + (conv_ch,), ll + ("tp",), dt, init_scale=0.0),
        "dt_bias": ParamSpec(L + (H,), ll + (None,), dt, init_scale=0.0),
        "A_log": ParamSpec(L + (H,), ll + (None,), dt, init_scale=0.0),
        "D_skip": ParamSpec(L + (H,), ll + (None,), dt, init_scale=0.0),
        "out_ln": ParamSpec(L + (d_inner,), ll + (None,), dt, init_scale=0.0),
        "out_proj": ParamSpec(L + (d_inner, D), ll + ("tp", "fsdp"), dt),
    }


def _split_proj(cfg: ArchConfig, proj):
    s = cfg.ssm
    d_inner, H, _ = mamba_dims(cfg)
    gn = s.n_groups * s.state
    z, xs, b, c, dt = jnp.split(
        proj, [d_inner, 2 * d_inner, 2 * d_inner + gn, 2 * d_inner + 2 * gn], axis=-1
    )
    return z, xs, b, c, dt


def _causal_conv(x, w, b, state=None, state_len=None):
    """Depthwise causal conv.  x: (B,T,C); w: (K,C); state: (B,K-1,C)|None.

    Returns (y, new_state) — new_state is the last K-1 inputs.  With
    ``state_len`` (a traced position, 1 <= state_len <= T) the state is
    instead the K-1 inputs *preceding position state_len*: the slotted
    serve engine prefills a right-padded length bucket, and the carried
    conv state must snapshot the real prompt end, not the padded tail.
    """
    K = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], K - 1, x.shape[-1]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    y = sum(
        xp[:, i : i + x.shape[1]] * w[i][None, None, :] for i in range(K)
    ) + b[None, None, :]
    if K <= 1:
        new_state = state
    elif state_len is None:
        new_state = xp[:, -(K - 1):]
    else:
        # xp[state_len : state_len + K - 1] = inputs at positions
        # [state_len - (K-1), state_len) — bitwise what an exact-length
        # (T == state_len) prefill would have carried
        new_state = jax.lax.dynamic_slice_in_dim(xp, state_len, K - 1, axis=1)
    return y, new_state


def group_rms_norm(x, gamma, groups: int, eps: float):
    """Mamba2's gated-output norm: RMSNorm over each of ``groups`` equal
    slices of the last axis (one slice is plain ``rms_norm``), gain
    ``1 + gamma`` over the whole axis."""
    dt = x.dtype
    xg = x.astype(jnp.float32).reshape(*x.shape[:-1], groups, -1)
    xg = xg * jax.lax.rsqrt(jnp.mean(jnp.square(xg), axis=-1, keepdims=True) + eps)
    return (xg.reshape(x.shape) * (1.0 + gamma.astype(jnp.float32))).astype(dt)


def mamba_block_fwd(cfg: ArchConfig, rules: ShardRules, x, bp, *,
                    return_state: bool = False, valid=None, state_len=None,
                    add=None):
    """x: (B,T,D).  Returns x + mamba(RMSNorm(x + add)) (and (ssm, conv)
    final states); ``add`` is a zamba2 hybrid layer's shared-block output.

    ``valid`` ((B,T) bool) marks real positions of a right-padded prompt
    bucket (slotted serve prefill): padded steps get ``dt = 0``, which is
    an *exact* identity on the SSD recurrence (decay ``exp(0) = 1``,
    input weight 0) — the same mechanism ``ssd_chunked`` uses for its own
    chunk padding — so the carried state is bitwise the state at the end
    of the real prompt.  ``state_len`` snapshots the conv state there too.
    """
    s = cfg.ssm
    d_inner, H, _ = mamba_dims(cfg)
    cdt = jnp.dtype(cfg.compute_dtype)
    h = rms_norm(x if add is None else x + add, bp["ln"], cfg.norm_eps)
    proj = jnp.einsum("btd,dk->btk", h, bp["in_proj"].astype(cdt))
    z, xs, bmat, cmat, dt = _split_proj(cfg, proj)
    conv_in = jnp.concatenate([xs, bmat, cmat], axis=-1)
    conv_out, conv_state = _causal_conv(
        conv_in, bp["conv_w"].astype(cdt), bp["conv_b"].astype(cdt),
        state_len=state_len,
    )
    conv_out = jax.nn.silu(conv_out)
    xs, bmat, cmat = jnp.split(conv_out, [d_inner, d_inner + s.n_groups * s.state], axis=-1)

    B_, T = x.shape[:2]
    xh = xs.reshape(B_, T, H, s.head_dim)
    bm = bmat.reshape(B_, T, s.n_groups, s.state)
    cm = cmat.reshape(B_, T, s.n_groups, s.state)
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + bp["dt_bias"].astype(jnp.float32))
    if valid is not None:
        dtv = jnp.where(valid[..., None], dtv, 0.0)
    A = -jnp.exp(bp["A_log"].astype(jnp.float32))
    with jax.named_scope("zamba.ssd"):
        y, ssm_state = ssd_chunked(xh, dtv, A, bm, cm, chunk=s.chunk,
                                   return_state=True)
    y = y + bp["D_skip"].astype(jnp.float32)[None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(B_, T, d_inner).astype(cdt)
    y = group_rms_norm(y * jax.nn.silu(z), bp["out_ln"], s.n_groups, cfg.norm_eps)
    out = jnp.einsum("btk,kd->btd", y, bp["out_proj"].astype(cdt))
    out = constrain(x + out, rules, "dp", "sp", None)
    if return_state:
        return out, (ssm_state, conv_state)
    return out


def mamba_state_specs(cfg: ArchConfig, n_layers: int, batch: int):
    s = cfg.ssm
    d_inner, H, conv_ch = mamba_dims(cfg)
    return {
        "ssm": jax.ShapeDtypeStruct((n_layers, batch, H, s.state, s.head_dim), jnp.float32),
        "conv": jax.ShapeDtypeStruct(
            (n_layers, batch, s.conv_kernel - 1, conv_ch), jnp.dtype(cfg.compute_dtype)
        ),
    }


def mamba_block_decode(cfg: ArchConfig, rules: ShardRules, x, bp, ssm_state,
                       conv_state, add=None):
    """x: (B,D) one token.  Returns (x', ssm_state', conv_state'); ``add``
    as in :func:`mamba_block_fwd`."""
    s = cfg.ssm
    d_inner, H, _ = mamba_dims(cfg)
    cdt = jnp.dtype(cfg.compute_dtype)
    h = rms_norm(x if add is None else x + add, bp["ln"], cfg.norm_eps)
    proj = jnp.einsum("bd,dk->bk", h, bp["in_proj"].astype(cdt))
    z, xs, bmat, cmat, dt = _split_proj(cfg, proj)
    conv_in = jnp.concatenate([xs, bmat, cmat], axis=-1)[:, None]
    conv_out, conv_state = _causal_conv(
        conv_in, bp["conv_w"].astype(cdt), bp["conv_b"].astype(cdt), conv_state
    )
    conv_out = jax.nn.silu(conv_out[:, 0])
    xs, bmat, cmat = jnp.split(conv_out, [d_inner, d_inner + s.n_groups * s.state], axis=-1)
    B_ = x.shape[0]
    xh = xs.reshape(B_, H, s.head_dim)
    bm = bmat.reshape(B_, s.n_groups, s.state)
    cm = cmat.reshape(B_, s.n_groups, s.state)
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + bp["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(bp["A_log"].astype(jnp.float32))
    with jax.named_scope("zamba.ssd"):
        ssm_state, y = ssd_decode_step(ssm_state, xh, dtv, A, bm, cm)
    y = y + bp["D_skip"].astype(jnp.float32)[None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(B_, d_inner).astype(cdt)
    y = group_rms_norm(y * jax.nn.silu(z), bp["out_ln"], s.n_groups, cfg.norm_eps)
    out = jnp.einsum("bk,kd->bd", y, bp["out_proj"].astype(cdt))
    return x + out, ssm_state, conv_state
