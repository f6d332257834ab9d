"""Plain reference for SmolLM-360M: the Llama decoder as the published
config.json describes it, in float32 ``jax.numpy``, with no kernels, cache
or batching tricks.  It imports nothing of the program under test.

Per layer: x += Wo . attn(RoPE(Wq h), RoPE(Wk h), Wv h) with h =
RMSNorm(x) * g1, grouped-query attention (each key/value head serves
num_attention_heads / num_key_value_heads consecutive query heads), causal
softmax scaled by head_dim ** -0.5; then x += Wd (silu(Wg h) * Wu h) with
h = RMSNorm(x) * g2.  Final RMSNorm, logits against the tied embedding,
mean token cross entropy.  RoPE rotates the two halves of each head
(Llama's rotate_half) with inverse frequencies theta ** (-2i / head_dim).

Departures from the published model: none in the mathematics.  Weights
are random from the seed (the published checkpoint is not used).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import precision

LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "wg", "wu", "wd")
EMBED_STD = 0.02    # the published config's initializer_range


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "f": cfg["intermediate_size"], "h": h,
            "hk": cfg["num_key_value_heads"], "dh": cfg.get("head_dim", d // h),
            "v": cfg["vocab_size"], "n": cfg["num_hidden_layers"]}


def shapes(cfg: dict) -> dict:
    s = dims(cfg)
    d, f, L, q, kv = s["d"], s["f"], s["n"], s["h"] * s["dh"], s["hk"] * s["dh"]
    return {
        "embed": (s["v"], d),
        "final_norm": (d,),
        "layers": {
            "attn_norm": (L, d), "wq": (L, d, q), "wk": (L, d, kv),
            "wv": (L, d, kv), "wo": (L, q, d), "mlp_norm": (L, d),
            "wg": (L, d, f), "wu": (L, d, f), "wd": (L, f, d),
        },
    }


def init(cfg: dict, key) -> dict:
    """Weights from a key: the embedding normal(0, 0.02); each layer's
    matrices normal(0, 1 / sqrt(fan_in)), so that every layer moves the
    residual stream as much as the token's own embedding does and greedy
    decoding does not merely repeat its input token; RMSNorm gains
    1 + normal(0, 0.1), so that the gains carry gradient of their own."""
    shp = shapes(cfg)
    flat = [("embed", shp["embed"]), ("final_norm", shp["final_norm"])] + [
        (k, shp["layers"][k]) for k in LAYER_KEYS]
    keys = jax.random.split(key, len(flat))
    out = {"layers": {}}
    for (name, shape), k in zip(flat, keys):
        if name.endswith("norm"):
            w = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif name == "embed":
            w = EMBED_STD * jax.random.normal(k, shape, jnp.float32)
        else:
            w = jax.random.normal(k, shape, jnp.float32) * shape[-2] ** -0.5
        (out["layers"] if name in LAYER_KEYS else out)[name] = w
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


def hidden(cfg: dict, w: dict, tokens, policy: str = "f32"):
    """Final normed hidden states (B, S, d) for token ids (B, S)."""
    s = dims(cfg)
    es = precision.einsum(policy)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    B, S = tokens.shape
    rep = s["h"] // s["hk"]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, lw):
        h = _rms_norm(x, lw["attn_norm"], eps)
        q = es("bsd,dk->bsk", h, lw["wq"]).reshape(B, S, s["h"], s["dh"])
        k = es("bsd,dk->bsk", h, lw["wk"]).reshape(B, S, s["hk"], s["dh"])
        v = es("bsd,dk->bsk", h, lw["wv"]).reshape(B, S, s["hk"], s["dh"])
        q, k = _rope(q, theta), _rope(k, theta)
        q = q.reshape(B, S, s["hk"], rep, s["dh"])
        sc = es("bqgrd,bkgd->bgrqk", q, k) * (s["dh"] ** -0.5)
        sc = jnp.where(causal, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        a = es("bgrqk,bkgd->bqgrd", p, v).reshape(B, S, s["h"] * s["dh"])
        x = x + es("bsk,kd->bsd", a, lw["wo"])
        h = _rms_norm(x, lw["mlp_norm"], eps)
        g = es("bsd,df->bsf", h, lw["wg"])
        u = es("bsd,df->bsf", h, lw["wu"])
        x = x + es("bsf,fd->bsd", jax.nn.silu(g) * u, lw["wd"])
        return x, None

    x = jnp.take(w["embed"], tokens, axis=0)
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, w["layers"])
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
