"""Zamba2 hybrid assembly [arXiv:2411.15242; transformers' Zamba2]: a
stack of Mamba2 layers, some of which (``hybrid_layer_ids``) first run a
shared transformer block (attention + MLP).

With x0 the token embedding and x the residual stream, layer i runs

    mamba:   x <- x + Mamba2(RMSNorm(x))
    hybrid, the k-th of ``hybrid_layer_ids``:
        u = RMSNorm(concat(x, x0))                              2 x d_model
        a = o_proj(Attn(RoPE(q_proj u), RoPE(k_proj u), v_proj u))
        [g, up] = gate_up(h) + B_k A_k h,  h = RMSNorm(a)
        t = down(act(g) * up)
        x <- x + Mamba2(RMSNorm(x + linear_k(t)))

The shared weights are those of block ``k % num_mem_blocks``; the rank
``adapter_rank`` LoRA (A_k, B_k) and ``linear_k`` belong to the k-th
hybrid layer, as do LoRA deltas on q/k/v where
``use_shared_attention_adapter`` is set.  Attention is causal over
``n_heads`` heads of ``head_dim`` (n_heads x head_dim = 2 x d_model),
scaled by ``query_scale``, RoPE over the whole head.  The shared block has
no residual of its own: its output reaches the stream only through the
layer's Mamba2 input.  A final RMSNorm, then logits against the tied
embedding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from .attention import (
    DecodeSharding, chunked_attention, decode_attention_pos_minor, rope,
)
from .common import (
    ACTIVATIONS, ParamSpec, ShardRules, constrain, constrain_spec,
    cross_entropy_loss, decode_positions, init_tree, remat_wrap, rms_norm,
)
from .lm import q_scale
from .ssm import (
    mamba_block_decode, mamba_block_fwd, mamba_block_specs, mamba_state_specs,
)


def runs(cfg: ArchConfig) -> list[tuple[int, int, int | None]]:
    """The layers as runs ``(first, end, k)``: a run starts at the k-th
    hybrid layer (``k`` None for the plain Mamba2 layers before the
    first) and holds the Mamba2 layers up to the next."""
    ids = sorted(cfg.hybrid_layer_ids)
    starts = ([0] if not ids or ids[0] > 0 else []) + ids
    out = []
    for j, a in enumerate(starts):
        b = starts[j + 1] if j + 1 < len(starts) else cfg.n_layers
        out.append((a, b, ids.index(a) if a in ids else None))
    return out


def param_specs(cfg: ArchConfig) -> dict:
    D, F, r = cfg.d_model, cfg.d_ff, cfg.adapter_rank
    A = 2 * D
    q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv * cfg.head_dim
    nb, nh = (cfg.num_mem_blocks,), (len(cfg.hybrid_layer_ids),)
    dt = jnp.dtype(cfg.param_dtype)
    shared = {
        "ln_attn": ParamSpec(nb + (A,), (None, None), dt, init_scale=0.0),
        "wq": ParamSpec(nb + (A, q), (None, "fsdp", "tp"), dt),
        "wk": ParamSpec(nb + (A, kv), (None, "fsdp", "tp"), dt),
        "wv": ParamSpec(nb + (A, kv), (None, "fsdp", "tp"), dt),
        "wo": ParamSpec(nb + (q, D), (None, "tp", "fsdp"), dt),
        "ln_mlp": ParamSpec(nb + (D,), (None, None), dt, init_scale=0.0),
        "w_gate_up": ParamSpec(nb + (D, 2 * F), (None, "fsdp", "tp"), dt),
        "w_down": ParamSpec(nb + (F, D), (None, "tp", "fsdp"), dt),
    }
    hybrid = {
        "mlp_a": ParamSpec(nh + (D, r), (None, "fsdp", None), dt),
        "mlp_b": ParamSpec(nh + (r, 2 * F), (None, None, "tp"), dt),
        "linear": ParamSpec(nh + (D, D), (None, "fsdp", "tp"), dt),
    }
    if cfg.use_shared_attention_adapter:
        for name, width in (("q", q), ("k", kv), ("v", kv)):
            hybrid[f"{name}_a"] = ParamSpec(nh + (A, r), (None, "fsdp", None), dt)
            hybrid[f"{name}_b"] = ParamSpec(nh + (r, width), (None, None, "tp"), dt)
    return {
        "embed": ParamSpec((cfg.vocab, D), ("tp", "fsdp"), dt),
        "ln_f": ParamSpec((D,), (None,), dt, init_scale=0.0),
        "mamba": mamba_block_specs(cfg, cfg.n_layers),
        "shared": shared,
        "hybrid": hybrid,
    }


def init(cfg: ArchConfig, key) -> dict:
    return init_tree(key, param_specs(cfg))


# ---------------------------------------------------------------------------


def _at(tree, i):
    return jax.tree.map(lambda p: p[i], tree)


def _shared_block(cfg: ArchConfig, params, k: int, x, x0, attend):
    """The k-th hybrid layer's shared block over (..., D) activations:
    returns (linear_k(t), what ``attend`` returned beside its output).
    ``attend(q, k, v)`` runs the attention core on the flat projections:
    over the sequence in prefill, against the lane cache in decode."""
    cdt = jnp.dtype(cfg.compute_dtype)
    sp = _at(params["shared"], k % cfg.num_mem_blocks)
    hp = _at(params["hybrid"], k)
    mm = lambda a, w: jnp.einsum("...d,dk->...k", a, w.astype(cdt))
    with jax.named_scope("zamba.shared_block"):
        u = rms_norm(jnp.concatenate([x, x0], axis=-1), sp["ln_attn"],
                     cfg.norm_eps)

        def proj(name):
            y = mm(u, sp[f"w{name}"])
            if cfg.use_shared_attention_adapter:
                y = y + mm(mm(u, hp[f"{name}_a"]), hp[f"{name}_b"])
            return y

        a, extra = attend(proj("q"), proj("k"), proj("v"))
        h = rms_norm(mm(a, sp["wo"]), sp["ln_mlp"], cfg.norm_eps)
        gate, up = jnp.split(
            mm(h, sp["w_gate_up"]) + mm(mm(h, hp["mlp_a"]), hp["mlp_b"]), 2,
            axis=-1)
        t = mm(ACTIVATIONS[cfg.gate_act](gate) * up, sp["w_down"])
        return mm(t, hp["linear"]), extra


def _embed(cfg, params, tokens):
    cdt = jnp.dtype(cfg.compute_dtype)
    return jnp.take(params["embed"].astype(cdt), tokens, axis=0)


def _logits(cfg, params, x):
    cdt = jnp.dtype(cfg.compute_dtype)
    return jnp.einsum("...d,vd->...v", x, params["embed"].astype(cdt))


def forward(cfg, mesh, rules, params, tokens, *, remat=True, collect=False,
            plen=None):
    """Returns (hidden, cache dict or None): with ``collect=True`` the
    second element is ``{"k", "v", "ssm", "conv"}`` — each hybrid layer's
    KV stacked in the cache's (nh, B, Hk, dh, S) layout plus every layer's
    Mamba2 final states — else ``None``.

    ``plen`` (traced scalar, slot-serving prefill only): positions beyond
    it are right-padding of a length bucket.  The attention KV of padded
    positions is inert by causality (standard slotted-cache argument);
    the *mamba* states are forced to snapshot position ``plen`` exactly
    (``dt = 0`` identity steps + conv state sliced at plen, see ssm.py).
    """
    x = _embed(cfg, params, tokens)
    valid = None
    if plen is not None:
        valid = (jnp.arange(tokens.shape[1]) < plen)[None, :]
        x = jnp.where(valid[..., None], x, 0.0)  # pad activations stay finite
    x0 = x
    x = constrain(x, rules, "dp", "sp", None)
    B, S = tokens.shape
    H, Hk, dh = cfg.n_heads, cfg.n_kv, cfg.head_dim
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def attend(q, k, v):
        q = rope(q.reshape(B, S, H, dh), positions, cfg.rope_theta) * q_scale(cfg)
        k = rope(k.reshape(B, S, Hk, dh), positions, cfg.rope_theta)
        v = v.reshape(B, S, Hk, dh)
        with jax.named_scope("attention"):
            o = chunked_attention(q, k, v, causal=True,
                                  q_chunk=min(256, S), kv_chunk=min(256, S))
        return o.reshape(B, S, H * dh), (k, v)

    def mamba(x, bp, add=None):
        with jax.named_scope("zamba.mamba"):
            if collect:
                return mamba_block_fwd(cfg, rules, x, bp, return_state=True,
                                       valid=valid, state_len=plen, add=add)
            return mamba_block_fwd(cfg, rules, x, bp, add=add), None

    def hybrid(x, k, bp):
        lt, kv = _shared_block(cfg, params, k, x, x0, attend)
        x, st = mamba(x, bp, lt)
        return x, (kv, st)

    kvs, states = [], []
    for a, b, k in runs(cfg):
        if k is not None:
            x, (kv, st) = remat_wrap(lambda x, bp, k=k: hybrid(x, k, bp),
                                     remat)(x, _at(params["mamba"], a))
            kvs.append(kv)
            states.append(jax.tree.map(lambda s: s[None], st))
            a += 1
        if b > a:
            seg = jax.tree.map(lambda p: p[a:b], params["mamba"])
            x, st = jax.lax.scan(remat_wrap(mamba, remat), x, seg)
            states.append(st)
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    if not collect:
        return x, None
    pos_minor = lambda i: jnp.moveaxis(jnp.stack([kv[i] for kv in kvs]), 2, -1)
    return x, {
        "k": pos_minor(0),
        "v": pos_minor(1),
        "ssm": jnp.concatenate([s[0] for s in states], axis=0),
        "conv": jnp.concatenate([s[1] for s in states], axis=0),
    }


def loss_fn(cfg, mesh, rules, params, batch, *, remat=True):
    tokens = batch["tokens"]
    inp, labels = tokens[:, :-1], tokens[:, 1:]
    hidden, _ = forward(cfg, mesh, rules, params, inp, remat=remat)
    logits = constrain(_logits(cfg, params, hidden), rules, "dp", None, "tp")
    loss = cross_entropy_loss(logits, labels)
    return loss, {"ce_loss": loss, "lb_loss": jnp.float32(0.0),
                  "drop_frac": jnp.float32(0.0)}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

# serve-engine state kind: each lane carries BOTH a slotted KV segment
# (the hybrid layers' attention, seq axis, lazily-overwritten) and
# per-lane recurrent mamba leaves (no seq axis, hard-reset) — the engine
# composes the two through one cache dict
STATE_KIND = "hybrid"


def recurrent_leaf_axes(cfg: ArchConfig) -> dict:
    """The mamba leaves are per-lane recurrent state (lane axis 1); ``k``
    and ``v`` stay on the KV lifecycle (lazy overwrite)."""
    return {"ssm": 1, "conv": 1}


def lane_leaf_axes(cfg: ArchConfig) -> dict:
    """All slot-cache leaves a lane owns (host-tier spill/restore unit):
    the slotted KV segment (lane axis 1, after the hybrid-layer axis)
    plus the recurrent mamba leaves."""
    return {"k": 1, "v": 1, **recurrent_leaf_axes(cfg)}


def make_cache_specs(cfg: ArchConfig, batch: int, max_len: int):
    """The hybrid layers' KV (nh, B, Hk, dh, S), position minor-most as
    in ``lm.make_cache_specs``, beside the Mamba2 leaves."""
    ms = mamba_state_specs(cfg, cfg.n_layers, batch)
    kv = jax.ShapeDtypeStruct(
        (len(cfg.hybrid_layer_ids), batch, cfg.n_kv, cfg.head_dim, max_len),
        jnp.dtype(cfg.compute_dtype))
    return {"k": kv, "v": kv, "ssm": ms["ssm"], "conv": ms["conv"]}


def cache_pspec(cfg: ArchConfig, dec: DecodeSharding):
    from jax.sharding import PartitionSpec as P
    b = dec.batch_axes or None
    s = dec.seq_axes or None
    return {
        "k": P(None, b, None, None, s),
        "v": P(None, b, None, None, s),
        "ssm": P(None, b, None, None, None),
        "conv": P(None, b, None, None),
    }


def prefill(cfg, mesh, rules, params, tokens, img_embeds=None, *, max_len=None):
    hidden, cache = forward(cfg, mesh, rules, params, tokens, remat=False, collect=True)
    dec = DecodeSharding.choose(mesh, tokens.shape[0])

    def pad(c):
        if max_len and max_len > c.shape[-1]:
            pw = [(0, 0)] * c.ndim
            pw[-1] = (0, max_len - c.shape[-1])
            c = jnp.pad(c, pw)
        return c

    cache["k"], cache["v"] = pad(cache["k"]), pad(cache["v"])
    specs = cache_pspec(cfg, dec)
    cache = {n: constrain_spec(c, mesh, specs[n]) for n, c in cache.items()}
    return cache, _logits(cfg, params, hidden[:, -1])


def decode_step(cfg, mesh, rules, params, cache, tokens, cur_index):
    x = _embed(cfg, params, tokens[:, None])[:, 0]
    x0 = x
    B = tokens.shape[0]
    H, Hk, dh = cfg.n_heads, cfg.n_kv, cfg.head_dim
    dec = DecodeSharding.choose(mesh, B)
    # scalar (aligned batch) or (B,) vector (slotted serve: per-lane
    # positions) — decode_attention_pos_minor handles both
    pos = decode_positions(cur_index, B)

    def attend_at(kc, vc):
        def attend(q, k, v):
            q = rope(q.reshape(B, 1, H, dh), pos, cfg.rope_theta)[:, 0] * q_scale(cfg)
            k = rope(k.reshape(B, 1, Hk, dh), pos, cfg.rope_theta)[:, 0]
            with jax.named_scope("attention"):
                o, kc2, vc2 = decode_attention_pos_minor(
                    q.reshape(B, Hk, H // Hk, dh), kc, vc, k,
                    v.reshape(B, Hk, dh), cur_index, sharding=dec)
            return o.reshape(B, H * dh), (kc2, vc2)
        return attend

    def mamba(x, xs, add=None):
        bp, s_ssm, s_conv = xs
        with jax.named_scope("zamba.mamba"):
            x, s_ssm, s_conv = mamba_block_decode(cfg, rules, x, bp, s_ssm,
                                                  s_conv, add)
        return x, (s_ssm, s_conv)

    # each hybrid layer's KV slice goes back to its place in the donated
    # stack (an in-place update; restacking would copy the whole leaf)
    kc_all, vc_all = cache["k"], cache["v"]
    states = []
    for a, b, k in runs(cfg):
        if k is not None:
            lt, (kc, vc) = _shared_block(cfg, params, k, x, x0,
                                         attend_at(kc_all[k], vc_all[k]))
            kc_all, vc_all = kc_all.at[k].set(kc), vc_all.at[k].set(vc)
            x, st = mamba(x, (_at(params["mamba"], a), cache["ssm"][a],
                              cache["conv"][a]), lt)
            states.append(jax.tree.map(lambda s: s[None], st))
            a += 1
        if b > a:
            xs = (jax.tree.map(lambda p: p[a:b], params["mamba"]),
                  cache["ssm"][a:b], cache["conv"][a:b])
            x, st = jax.lax.scan(mamba, x, xs)
            states.append(st)
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    new_cache = {
        "k": kc_all,
        "v": vc_all,
        "ssm": jnp.concatenate([s[0] for s in states], axis=0),
        "conv": jnp.concatenate([s[1] for s in states], axis=0),
    }
    return _logits(cfg, params, x), new_cache


def prefill_slot(cfg, mesh, rules, params, cache, tokens, slot, plen):
    """Prefill ONE prompt into lane ``slot`` of the composed hybrid cache.

    tokens: (1, S_bucket) right-padded; ``plen``/``slot`` traced scalars.
    The lane write covers both state kinds at once: the hybrid layers'
    K/V land in the lane's seq slice ``[0, S_bucket)`` (padded tail inert
    by causality + lazy overwrite, exactly the lm slotted argument) and
    the mamba ``ssm``/``conv`` leaves land as the lane's O(1) recurrent
    snapshot at position ``plen`` (dt=0 identity padding, see ssm.py).
    Returns (cache', logits (1, V) at position plen - 1).
    """
    hidden, col = forward(
        cfg, mesh, rules, params, tokens, remat=False, collect=True,
        plen=plen,
    )

    def write(c, n):
        start = (0, slot) + (0,) * (c.ndim - 2)
        return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), start)

    cache = {name: write(cache[name], col[name]) for name in cache}
    last = jax.lax.dynamic_index_in_dim(hidden, plen - 1, 1, keepdims=False)
    return cache, _logits(cfg, params, last)
