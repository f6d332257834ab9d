"""Plain reference for Zamba2-7B-Instruct: the Zamba2 model as the
published config.json and transformers' Zamba2 modeling describe it, in
float32 ``jax.numpy``, with no kernels, cache or batching tricks.  It
imports nothing of the program under test.

With x0 the token embedding and x the residual stream, a ``mamba`` layer
is x += Mamba2(RMSNorm(x)).  The k-th ``hybrid`` layer first runs shared
block k mod num_mem_blocks on u = RMSNorm(concat(x, x0)) (width
attention_hidden_size): causal multi-head attention of
num_attention_heads heads of attention_head_dim, RoPE over the whole head
(rotate_half, inverse frequencies theta ** (-2i / head_dim)), scale
(attention_head_dim / 2) ** -0.5, then o_proj to hidden_size; then h =
RMSNorm(a) and t = down(gelu(g) * up) with [g, up] = gate_up(h) + B_k A_k h
(GELU in its exact erf form, the layer's own rank adapter_rank LoRA).  The
layer then runs x += Mamba2(RMSNorm(x + linear_k(t))).  Final RMSNorm,
logits against the tied embedding.

Mamba2: in_proj gives [z, x, B, C, dt] (mamba_ngroups groups of
mamba_d_state for B and C), a depthwise causal conv of width mamba_d_conv
with bias over [x, B, C] then SiLU, dt = softplus(dt + dt_bias), A =
-exp(A_log), the SSM run as a sequential scan over positions (head h reads
group h // (heads / groups)), y + D x, then out_proj(GroupRMSNorm(y *
silu(z))), the norm taken over each group's slice of the inner width.

Departures from the published model: none in the mathematics.  Weights
are random from the seed: the embedding normal(0, 0.02); projections,
adapters and conv taps normal(0, 1 / sqrt(fan_in)); RMSNorm gains 1 +
normal(0, 0.1); A_log = log U[1, 16]; dt_bias the inverse softplus of dt
drawn log-uniform in [time_step_min, time_step_max] and floored at
time_step_floor; D = 1; conv bias 0.  Embeddings are tied (assumed).  The
attention adapters of ``use_shared_attention_adapter`` are not modelled:
the configuration has them off.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import precision

EMBED_STD = 0.02    # Zamba2's default initializer_range
Q_BLOCK = 512       # query rows per attention block


def dims(cfg: dict) -> dict:
    d, G, N = cfg["hidden_size"], cfg["mamba_ngroups"], cfg["mamba_d_state"]
    inner = cfg["mamba_expand"] * d
    return {"d": d, "f": cfg["intermediate_size"], "v": cfg["vocab_size"],
            "n": cfg["num_hidden_layers"], "a": cfg["attention_hidden_size"],
            "h": cfg["num_attention_heads"], "dh": cfg["attention_head_dim"],
            "r": cfg["adapter_rank"], "m": cfg["num_mem_blocks"],
            "nh": len(cfg["hybrid_layer_ids"]), "inner": inner,
            "sh": cfg["n_mamba_heads"], "p": cfg["mamba_headdim"], "g": G,
            "ns": N, "k": cfg["mamba_d_conv"], "conv": inner + 2 * G * N}


def shapes(cfg: dict) -> dict:
    s = dims(cfg)
    d, f, a, L, M, NH = s["d"], s["f"], s["a"], s["n"], s["m"], s["nh"]
    q = s["h"] * s["dh"]
    return {
        "embed": (s["v"], d),
        "final_norm": (d,),
        "mamba": {
            "norm": (L, d),
            "in_proj": (L, d, 2 * s["inner"] + 2 * s["g"] * s["ns"] + s["sh"]),
            "conv_w": (L, s["k"], s["conv"]), "conv_b": (L, s["conv"]),
            "dt_bias": (L, s["sh"]), "A_log": (L, s["sh"]), "D": (L, s["sh"]),
            "gated_norm": (L, s["inner"]), "out_proj": (L, s["inner"], d),
        },
        "blocks": {
            "attn_norm": (M, a), "q_proj": (M, a, q), "k_proj": (M, a, q),
            "v_proj": (M, a, q), "o_proj": (M, q, d), "mlp_norm": (M, d),
            "gate_up": (M, d, 2 * f), "down": (M, f, d),
        },
        "hybrid": {
            "adapter_a": (NH, d, s["r"]), "adapter_b": (NH, s["r"], 2 * f),
            "linear": (NH, d, d),
        },
    }


def init(cfg: dict, key) -> dict:
    """Weights from a key, as the module docstring says."""
    shp = shapes(cfg)
    flat = [(g, n, sh) for g in ("mamba", "blocks", "hybrid")
            for n, sh in shp[g].items()]
    flat = [(None, "embed", shp["embed"]), (None, "final_norm", shp["final_norm"])] + flat
    keys = jax.random.split(key, len(flat))
    out = {"mamba": {}, "blocks": {}, "hybrid": {}}
    for (group, name, shape), k in zip(flat, keys):
        if name.endswith("norm"):
            w = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif name == "embed":
            w = EMBED_STD * jax.random.normal(k, shape, jnp.float32)
        elif name == "A_log":
            w = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":
            lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo, hi))
            dt = jnp.maximum(dt, cfg["time_step_floor"])
            w = dt + jnp.log(-jnp.expm1(-dt))            # softplus(w) == dt
        elif name == "D":
            w = jnp.ones(shape, jnp.float32)
        elif name == "conv_b":
            w = jnp.zeros(shape, jnp.float32)
        else:
            w = jax.random.normal(k, shape, jnp.float32) * shape[-2] ** -0.5
        (out[group] if group else out)[name] = w
    return out


def _rms_norm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (B, S, H, dh)."""
    S, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dh)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv          # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, es):
    """Causal softmax attention, one block of query rows at a time.
    q, k, v: (B, S, H, dh), q already scaled."""
    B, S, H, dh = q.shape
    blk = min(Q_BLOCK, S)
    while S % blk:
        blk -= 1
    pos_k = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        sc = es("bqhd,bkhd->bhqk", qb, k)
        pos_q = i * blk + jnp.arange(blk)
        sc = jnp.where(pos_k[None, :] <= pos_q[:, None], sc, -jnp.inf)
        return es("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v)

    out = jax.lax.map(block, jnp.arange(S // blk))        # (nb, B, blk, H, dh)
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, dh)


def ssd_scan(x, dt, A, Bm, Cm):
    """The SSM, one position at a time: S <- exp(dt A) S + dt B x^T,
    y = C . S.  x: (B, S, H, P); dt: (B, S, H); A: (H,); Bm, Cm:
    (B, S, G, N), head h reading group h // (H / G).  Returns y."""
    rep = x.shape[2] // Bm.shape[2]
    Bh = jnp.repeat(Bm, rep, axis=2)
    Ch = jnp.repeat(Cm, rep, axis=2)

    def step(state, inp):
        xt, dtt, bt, ct = inp
        state = state * jnp.exp(dtt * A)[..., None, None] + jnp.einsum(
            "bhn,bhp->bhnp", bt, xt * dtt[..., None],
            precision=jax.lax.Precision.HIGHEST)
        return state, jnp.einsum("bhn,bhnp->bhp", ct, state,
                                 precision=jax.lax.Precision.HIGHEST)

    Bsz, _, H, P = x.shape
    state0 = jnp.zeros((Bsz, H, Bm.shape[-1], P), jnp.float32)
    seq = lambda a: jnp.moveaxis(a, 1, 0)
    _, ys = jax.lax.scan(step, state0, (seq(x), seq(dt), seq(Bh), seq(Ch)))
    return jnp.moveaxis(ys, 0, 1)


def _mamba(cfg, s, lw, x, es):
    """Mamba2 mixer over (B, S, hidden) normed input."""
    Bsz, S, _ = x.shape
    I, G, N, H = s["inner"], s["g"], s["ns"], s["sh"]
    z, xbc, dt = jnp.split(es("bsd,dk->bsk", x, lw["in_proj"]),
                           [I, I + s["conv"]], axis=-1)
    K = s["k"]
    xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = sum(xp[:, i:i + S] * lw["conv_w"][i] for i in range(K)) + lw["conv_b"]
    xbc = jax.nn.silu(xbc)
    xs, Bm, Cm = jnp.split(xbc, [I, I + G * N], axis=-1)
    xs = xs.reshape(Bsz, S, H, s["p"])
    dt = jax.nn.softplus(dt + lw["dt_bias"])
    y = ssd_scan(xs, dt, -jnp.exp(lw["A_log"]), Bm.reshape(Bsz, S, G, N),
                 Cm.reshape(Bsz, S, G, N))
    y = (y + lw["D"][:, None] * xs).reshape(Bsz, S, I) * jax.nn.silu(z)
    yg = y.reshape(Bsz, S, G, I // G)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True)
                            + cfg["rms_norm_eps"])
    y = yg.reshape(Bsz, S, I) * lw["gated_norm"]
    return es("bsk,kd->bsd", y, lw["out_proj"])


def _shared(cfg, s, bw, hw, x, x0, es):
    """Shared block ``bw`` with one hybrid layer's adapter and linear
    ``hw``: returns linear(t)."""
    Bsz, S, _ = x.shape
    eps = cfg["rms_norm_eps"]
    u = _rms_norm(jnp.concatenate([x, x0], -1), bw["attn_norm"], eps)
    heads = lambda w: es("bsa,ak->bsk", u, w).reshape(Bsz, S, s["h"], s["dh"])
    theta = cfg["rope_theta"]
    q = _rope(heads(bw["q_proj"]), theta) * (s["dh"] / 2) ** -0.5
    k = _rope(heads(bw["k_proj"]), theta)
    a = _attention(q, k, heads(bw["v_proj"]), es).reshape(Bsz, S, -1)
    h = _rms_norm(es("bsk,kd->bsd", a, bw["o_proj"]), bw["mlp_norm"], eps)
    gu = es("bsd,df->bsf", h, bw["gate_up"]) + es(
        "bsr,rf->bsf", es("bsd,dr->bsr", h, hw["adapter_a"]), hw["adapter_b"])
    g, up = jnp.split(gu, 2, axis=-1)
    t = es("bsf,fd->bsd", jax.nn.gelu(g, approximate=False) * up, bw["down"])
    return es("bsd,de->bse", t, hw["linear"])


def hidden(cfg: dict, w: dict, tokens, policy: str = "f32"):
    """Final normed hidden states (B, S, d) for token ids (B, S)."""
    if cfg["use_shared_attention_adapter"]:
        raise ValueError("the attention adapters are not modelled")
    s = dims(cfg)
    es = precision.einsum(policy)
    eps = cfg["rms_norm_eps"]
    at = lambda tree, i: jax.tree.map(lambda p: p[i], tree)
    x0 = jnp.take(w["embed"], tokens, axis=0)
    x = x0
    hybrid = list(cfg["hybrid_layer_ids"])
    for i, kind in enumerate(cfg["layers_block_type"]):
        lw = at(w["mamba"], i)
        inp = x
        if kind == "hybrid":
            k = hybrid.index(i)
            inp = x + _shared(cfg, s, at(w["blocks"], k % s["m"]),
                              at(w["hybrid"], k), x, x0, es)
        x = x + _mamba(cfg, s, lw, _rms_norm(inp, lw["norm"], eps), es)
    return _rms_norm(x, w["final_norm"], eps)


def logits(cfg: dict, w: dict, tokens, policy: str = "f32"):
    """(B, S, vocab) float32 logits against the tied embedding."""
    return precision.einsum(policy)(
        "bsd,vd->bsv", hidden(cfg, w, tokens, policy), w["embed"])


def loss(cfg: dict, w: dict, tokens, policy: str = "f32"):
    """Mean next-token cross entropy over every position of (B, S + 1)."""
    lg = logits(cfg, w, tokens[:, :-1], policy)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
