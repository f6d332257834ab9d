"""Decoder-only LM assembly: dense (llama/deepseek/stablelm/smollm),
gemma2 (alternating local/global attention + softcaps + post-norms),
qwen3-MoE (expert-parallel FFN), and InternVL-style VLM (stubbed vision
frontend projected into the sequence).

Layers are stacked on a leading axis and executed with ``lax.scan`` (pairs
of (local, global) layers for gemma2), which keeps HLO size independent of
depth — essential for 95-layer models partitioned over 512 devices.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.configs.base import ArchConfig
from .attention import (
    DecodeSharding,
    chunked_attention,
    decode_attention_pos_minor,
    paged_decode_attention,
    paged_gather,
    paged_write_positions,
    rope,
)
from .common import (
    ACTIVATIONS,
    ParamSpec,
    ShardRules,
    constrain,
    cross_entropy_loss,
    decode_positions,
    in_manual_mode,
    init_tree,
    rms_norm,
    softcap,
    wuse,
)
from .moe import moe_ffn

# ---------------------------------------------------------------------------
# Parameter declaration
# ---------------------------------------------------------------------------


def _leading(cfg: ArchConfig) -> tuple[int, ...]:
    if cfg.alt_local_global:
        assert cfg.n_layers % 2 == 0, "alternating archs need an even layer count"
        return (cfg.n_layers // 2, 2)
    return (cfg.n_layers,)


def _lead_logical(cfg: ArchConfig) -> tuple[None, ...]:
    return (None,) * len(_leading(cfg))


def block_specs(cfg: ArchConfig) -> dict:
    lead, ll = _leading(cfg), _lead_logical(cfg)
    D, dh, H, Hk = cfg.d_model, cfg.head_dim, cfg.n_heads, cfg.n_kv
    dt = jnp.dtype(cfg.param_dtype)
    s: dict[str, ParamSpec] = {
        "ln1": ParamSpec(lead + (D,), ll + (None,), dt, init_scale=0.0),
        "ln2": ParamSpec(lead + (D,), ll + (None,), dt, init_scale=0.0),
        "wq": ParamSpec(lead + (D, H * dh), ll + ("fsdp", "tp"), dt),
        "wk": ParamSpec(lead + (D, Hk * dh), ll + ("fsdp", "tp"), dt),
        "wv": ParamSpec(lead + (D, Hk * dh), ll + ("fsdp", "tp"), dt),
        "wo": ParamSpec(lead + (H * dh, D), ll + ("tp", "fsdp"), dt),
    }
    if cfg.qk_norm:
        s["qnorm"] = ParamSpec(lead + (dh,), ll + (None,), dt, init_scale=0.0)
        s["knorm"] = ParamSpec(lead + (dh,), ll + (None,), dt, init_scale=0.0)
    if cfg.alt_local_global:  # gemma2 post-norms
        s["ln1b"] = ParamSpec(lead + (D,), ll + (None,), dt, init_scale=0.0)
        s["ln2b"] = ParamSpec(lead + (D,), ll + (None,), dt, init_scale=0.0)
    if cfg.moe.num_experts:
        E, F = cfg.moe.num_experts, cfg.moe.d_expert
        s["router"] = ParamSpec(lead + (D, E), ll + (None, None), dt)
        s["wg_e"] = ParamSpec(lead + (E, D, F), ll + ("tp", "fsdp", None), dt)
        s["wu_e"] = ParamSpec(lead + (E, D, F), ll + ("tp", "fsdp", None), dt)
        s["wd_e"] = ParamSpec(lead + (E, F, D), ll + ("tp", None, "fsdp"), dt)
    else:
        F = cfg.d_ff
        s["wg"] = ParamSpec(lead + (D, F), ll + ("fsdp", "tp"), dt)
        s["wu"] = ParamSpec(lead + (D, F), ll + ("fsdp", "tp"), dt)
        s["wd"] = ParamSpec(lead + (F, D), ll + ("tp", "fsdp"), dt)
    return s


def param_specs(cfg: ArchConfig) -> dict:
    dt = jnp.dtype(cfg.param_dtype)
    D = cfg.d_model
    s = {
        "embed": ParamSpec((cfg.vocab, D), ("tp", "fsdp"), dt),
        "ln_f": ParamSpec((D,), (None,), dt, init_scale=0.0),
        "blocks": block_specs(cfg),
    }
    if not cfg.tie_embeddings:
        s["unembed"] = ParamSpec((D, cfg.vocab), ("fsdp", "tp"), dt)
    if cfg.family == "vlm":
        s["img_proj"] = ParamSpec((cfg.frontend_dim, D), (None, "fsdp"), dt)
    return s


def init(cfg: ArchConfig, key) -> dict:
    return init_tree(key, param_specs(cfg))


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _gate(cfg: ArchConfig, g):
    return ACTIVATIONS[cfg.gate_act](g)


def q_scale(cfg: ArchConfig) -> float:
    # chunked_attention applies dh**-0.5; fold any override into q.
    if cfg.query_scale:
        return cfg.query_scale * (cfg.head_dim ** 0.5)
    return 1.0


def _tp_size(mesh: Mesh, rules: ShardRules) -> int:
    return mesh.shape[rules.tp] if rules.tp and rules.tp in mesh.axis_names else 1


def _attn_proj(cfg, mesh, rules, h, bp, positions):
    B, S, _ = h.shape
    dh, H, Hk = cfg.head_dim, cfg.n_heads, cfg.n_kv
    cdt = jnp.dtype(cfg.compute_dtype)
    q = jnp.einsum("bsd,dk->bsk", h, wuse(bp["wq"], rules, "fsdp", "tp", dtype=cdt)).reshape(B, S, H, dh)
    k = jnp.einsum("bsd,dk->bsk", h, wuse(bp["wk"], rules, "fsdp", "tp", dtype=cdt)).reshape(B, S, Hk, dh)
    v = jnp.einsum("bsd,dk->bsk", h, wuse(bp["wv"], rules, "fsdp", "tp", dtype=cdt)).reshape(B, S, Hk, dh)
    if cfg.qk_norm:
        q = rms_norm(q, bp["qnorm"], cfg.norm_eps)
        k = rms_norm(k, bp["knorm"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta) * q_scale(cfg)
    k = rope(k, positions, cfg.rope_theta)
    tp = _tp_size(mesh, rules)
    q = constrain(q, rules, "dp", None, "tp" if H % tp == 0 else None, None)
    k = constrain(k, rules, "dp", None, "tp" if Hk % tp == 0 else None, None)
    return q, k, v


def _ffn(cfg, mesh, rules, x, bp):
    """Returns (ffn_out, aux)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    if cfg.moe.num_experts:
        return moe_ffn(
            x, bp["router"], bp["wg_e"], bp["wu_e"], bp["wd_e"],
            cfg=cfg, mesh=mesh, rules=rules,
        )
    g = jnp.einsum("bsd,df->bsf", x, wuse(bp["wg"], rules, "fsdp", "tp", dtype=cdt))
    u = jnp.einsum("bsd,df->bsf", x, wuse(bp["wu"], rules, "fsdp", "tp", dtype=cdt))
    h = _gate(cfg, g) * u
    h = constrain(h, rules, "dp", None, "tp")
    out = jnp.einsum("bsf,fd->bsd", h, wuse(bp["wd"], rules, "tp", "fsdp", dtype=cdt))
    out = constrain(out, rules, "dp", "sp", None)   # psum -> reduce-scatter
    return out, {"lb_loss": jnp.float32(0.0), "drop_frac": jnp.float32(0.0)}


def _attn_impl(cfg, mesh, q, k) -> str:
    """The attention path of ``_block_fwd``: ``cfg.attn_impl``, with
    "auto" resolved from what the call shows.  The Pallas flash kernels
    take the attention where they apply (``flash_applies``) and the
    attention runs on one device's data (a one-device mesh, or a
    per-worker program): a Pallas call has no rule to partition it."""
    if cfg.attn_impl != "auto":
        return cfg.attn_impl
    from repro.kernels.flash_attention.ops import flash_applies
    per_device = mesh.size == 1 or in_manual_mode()
    if per_device and flash_applies(q.shape, k.shape, q.dtype, causal=True,
                                    softcap=cfg.attn_softcap):
        return "pallas"
    return "chunked"


def _block_fwd(cfg, mesh, rules, x, bp, positions, *, window: int,
               collect_kv: bool, attn_fn=None):
    """One transformer block, training/prefill path.

    ``attn_fn(q, k, v, window) -> (attn, extra)`` overrides the attention
    step (the chunked-prefill path writes K/V through a block table and
    attends against the lane's cache); everything around it — projections,
    norms, residuals, FFN — is shared so the paths stay numerically
    identical.  Returns (x, aux, kv): kv is (k, v) when ``collect_kv``,
    else ``attn_fn``'s extra (None on the default path).
    """
    h = rms_norm(x, bp["ln1"], cfg.norm_eps)
    h = constrain(h, rules, "dp", "sp", None)
    q, k, v = _attn_proj(cfg, mesh, rules, h, bp, positions)
    extra = None
    # the attention core alone (projections are outside): a trace
    # reduction reads its device time by this scope, forward and backward
    with jax.named_scope("attention"):
        if attn_fn is not None:
            attn, extra = attn_fn(q, k, v, window)
        elif _attn_impl(cfg, mesh, q, k) == "pallas":
            # the flash kernels, forward and backward, skipping masked
            # blocks (checked against the reference in tests/test_kernels.py)
            from repro.kernels import flash_attention
            attn = flash_attention(
                q, k, v, causal=True, window=window, softcap=cfg.attn_softcap,
            )
        else:
            attn = chunked_attention(
                q, k, v,
                causal=True,
                window=window,
                softcap=cfg.attn_softcap,
                q_chunk=min(256, q.shape[1]),
                kv_chunk=min(256, k.shape[1]),
            )
    B, S = x.shape[:2]
    cdt = jnp.dtype(cfg.compute_dtype)
    o = jnp.einsum(
        "bsk,kd->bsd", attn.reshape(B, S, -1), wuse(bp["wo"], rules, "tp", "fsdp", dtype=cdt)
    )
    # pin the psum output BEFORE the residual add so the TP partial sum
    # lowers to reduce-scatter (all-reduce + slice after the add costs 2x)
    o = constrain(o, rules, "dp", "sp", None)
    if cfg.alt_local_global:
        o = rms_norm(o, bp["ln1b"], cfg.norm_eps)
    x = constrain(x + o, rules, "dp", "sp", None)
    h2 = rms_norm(x, bp["ln2"], cfg.norm_eps)
    h2 = constrain(h2, rules, "dp", "sp", None)
    ffn, aux = _ffn(cfg, mesh, rules, h2, bp)
    if cfg.alt_local_global:
        ffn = rms_norm(ffn, bp["ln2b"], cfg.norm_eps)
    x = constrain(x + ffn, rules, "dp", "sp", None)
    kv = (k, v) if collect_kv else extra
    return x, aux, kv


def _block_decode(cfg, mesh, rules, x, bp, kc, vc, cur_index, *, window: int,
                  dec_sharding: DecodeSharding | None, attn_fn=None):
    """One block, single-token decode. x: (B, D). Returns (x, kc, vc).

    ``attn_fn(q, kc, vc, k_new, v_new, window)`` overrides the cache-write
    + attention step (the paged path); the default is the slotted
    position-minor ``decode_attention_pos_minor`` under ``dec_sharding``.
    Everything around the attention call is shared between the layouts.
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    dh, H, Hk = cfg.head_dim, cfg.n_heads, cfg.n_kv
    B = x.shape[0]
    h = rms_norm(x, bp["ln1"], cfg.norm_eps)
    q = jnp.einsum("bd,dk->bk", h, bp["wq"].astype(cdt)).reshape(B, H, dh)
    k = jnp.einsum("bd,dk->bk", h, bp["wk"].astype(cdt)).reshape(B, Hk, dh)
    v = jnp.einsum("bd,dk->bk", h, bp["wv"].astype(cdt)).reshape(B, Hk, dh)
    if cfg.qk_norm:
        q = rms_norm(q, bp["qnorm"], cfg.norm_eps)
        k = rms_norm(k, bp["knorm"], cfg.norm_eps)
    pos = decode_positions(cur_index, B)
    q = rope(q[:, None], pos, cfg.rope_theta)[:, 0] * q_scale(cfg)
    k = rope(k[:, None], pos, cfg.rope_theta)[:, 0]
    q = q.reshape(B, Hk, H // Hk, dh)
    if attn_fn is None:
        attn, kc, vc = decode_attention_pos_minor(
            q, kc, vc, k, v, cur_index,
            sharding=dec_sharding, window=window, softcap=cfg.attn_softcap,
        )
    else:
        attn, kc, vc = attn_fn(q, kc, vc, k, v, window)
    o = jnp.einsum("bk,kd->bd", attn.reshape(B, H * dh), bp["wo"].astype(cdt))
    if cfg.alt_local_global:
        o = rms_norm(o, bp["ln1b"], cfg.norm_eps)
    x = x + o
    h2 = rms_norm(x, bp["ln2"], cfg.norm_eps)
    ffn, _ = _ffn(cfg, mesh, rules, h2[:, None], bp)
    ffn = ffn[:, 0]
    if cfg.alt_local_global:
        ffn = rms_norm(ffn, bp["ln2b"], cfg.norm_eps)
    return x + ffn, kc, vc


def _sub(tree, i):
    return jax.tree.map(lambda p: p[i], tree)


def _windows(cfg: ArchConfig) -> tuple[int, ...]:
    """Window per sub-block within a scan step."""
    if cfg.alt_local_global:
        return (cfg.window, 0)       # (local, global)
    return (cfg.window,)             # 0 => full causal


# ---------------------------------------------------------------------------
# Forward / loss
# ---------------------------------------------------------------------------


def embed_tokens(cfg, rules, params, tokens):
    cdt = jnp.dtype(cfg.compute_dtype)
    x = jnp.take(wuse(params["embed"], rules, "tp", "fsdp", dtype=cdt), tokens, axis=0)
    if cfg.alt_local_global:   # gemma scales embeddings
        x = x * jnp.asarray(np.sqrt(cfg.d_model), cdt)
    return x


def unembed(cfg, rules, params, x):
    cdt = jnp.dtype(cfg.compute_dtype)
    if cfg.tie_embeddings:
        w = wuse(params["embed"], rules, "tp", "fsdp", dtype=cdt).T
    else:
        w = wuse(params["unembed"], rules, "fsdp", "tp", dtype=cdt)
    logits = jnp.einsum("...d,dv->...v", x, w)
    logits = constrain(logits, rules, "dp", None, "tp") if logits.ndim == 3 \
        else constrain(logits, rules, "dp", "tp")
    if cfg.logit_softcap:
        logits = softcap(logits.astype(jnp.float32), cfg.logit_softcap)
    return logits


def forward(cfg: ArchConfig, mesh: Mesh, rules: ShardRules, params, tokens,
            img_embeds=None, *, remat: bool = True, collect_kv: bool = False):
    """Returns (hidden (B,S,D), aux, kv_stack or None)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    x = embed_tokens(cfg, rules, params, tokens)
    if cfg.family == "vlm":
        img = jnp.einsum(
            "bnf,fd->bnd", img_embeds.astype(cdt), params["img_proj"].astype(cdt)
        )
        x = jnp.concatenate([img, x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = constrain(x, rules, "dp", "sp", None)

    windows = _windows(cfg)

    def body(carry, bp):
        x, lb, dr = carry
        kvs = []
        for i, w in enumerate(windows):
            sub_bp = _sub(bp, i) if len(windows) > 1 else bp
            x, aux, kv = _block_fwd(
                cfg, mesh, rules, x, sub_bp, positions,
                window=w, collect_kv=collect_kv,
            )
            lb, dr = lb + aux["lb_loss"], jnp.maximum(dr, aux["drop_frac"])
            kvs.append(kv)
        if collect_kv:
            ys = jax.tree.map(lambda *xs: jnp.stack(xs), *kvs) if len(kvs) > 1 else kvs[0]
        else:
            ys = None
        return (x, lb, dr), ys

    from .common import remat_wrap
    body = remat_wrap(body, remat)
    (x, lb, dr), kv_stack = jax.lax.scan(
        body, (x, jnp.float32(0.0), jnp.float32(0.0)), params["blocks"]
    )
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    return x, {"lb_loss": lb, "drop_frac": dr}, kv_stack


def loss_fn(cfg: ArchConfig, mesh: Mesh, rules: ShardRules, params, batch,
            *, remat: bool = True):
    tokens = batch["tokens"]                    # (B, S_text + 1)
    inp, labels = tokens[:, :-1], tokens[:, 1:]
    img = batch.get("patch_embeds")
    hidden, aux, _ = forward(cfg, mesh, rules, params, inp, img, remat=remat)
    if cfg.family == "vlm":
        n = cfg.frontend_tokens
        hidden = hidden[:, n - 1 : n - 1 + labels.shape[1]]
    logits = unembed(cfg, rules, params, hidden)
    loss = cross_entropy_loss(logits, labels)
    total = loss + 1e-2 * aux["lb_loss"] / max(cfg.n_layers, 1)
    return total, {"ce_loss": loss, **aux}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


def make_cache_specs(cfg: ArchConfig, batch: int, max_len: int):
    """Abstract slotted KV cache (lead..., B, Hk, dh, S) as
    ShapeDtypeStructs.  The position axis is minor-most, so the TPU stores
    a lane's (Hk, dh, S) unpadded and decode reads and writes it in place
    (see ``attention.decode_attention_pos_minor``)."""
    lead = _leading(cfg)
    shape = lead + (batch, cfg.n_kv, cfg.head_dim, max_len)
    c = jax.ShapeDtypeStruct(shape, jnp.dtype(cfg.compute_dtype))
    return {"k": c, "v": c}


def lane_leaf_axes(cfg: ArchConfig) -> dict:
    """{cache leaf name -> lane axis} for the *slotted* cache — everything
    one lane owns, used by the host tier to spill/restore a whole lane as
    one copy.  For the lm families both leaves put the lane right after
    the leading (layer[, k/v]) axes."""
    lead = len(_leading(cfg))
    return {"k": lead, "v": lead}


def cache_pspec(cfg: ArchConfig, dec: DecodeSharding):
    lead = (None,) * len(_leading(cfg))
    from jax.sharding import PartitionSpec as P
    spec = P(*lead, dec.batch_axes or None, None, None, dec.seq_axes or None)
    return {"k": spec, "v": spec}


def make_paged_cache_specs(cfg: ArchConfig, num_blocks: int, block_size: int):
    """Abstract paged KV pool (lead..., NB, bs, Hk, dh)."""
    lead = _leading(cfg)
    shape = lead + (num_blocks, block_size, cfg.n_kv, cfg.head_dim)
    c = jax.ShapeDtypeStruct(shape, jnp.dtype(cfg.compute_dtype))
    return {"k": c, "v": c}


def paged_cache_pspec(cfg: ArchConfig, mesh: Mesh, num_blocks: int = 0):
    """Pool sharding: blocks over the data axes (so per-device reservation
    shrinks with DP size — matching how the slotted cache batch-shards its
    lanes; table gathers become collectives, a bandwidth-for-HBM trade)
    and KV heads over the tensor axis, each when divisible."""
    lead = (None,) * len(_leading(cfg))
    from jax.sharding import PartitionSpec as P
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    ndp = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    blk = dp if (dp and num_blocks and num_blocks % ndp == 0) else None
    tp = "model" if (
        "model" in mesh.axis_names and cfg.n_kv % mesh.shape["model"] == 0
    ) else None
    spec = P(*lead, blk, None, tp, None)
    return {"k": spec, "v": spec}


def copy_paged_block(cfg: ArchConfig, cache, src, dst):
    """Copy physical pool block ``src`` to ``dst`` in every cache leaf —
    the copy-on-write step of prefix caching: when a new request's prompt
    fully covers a shared block but must rewrite its tail position (the
    sampling position is always recomputed), the engine clones the block
    and hands the lane the private copy.

    Leaves are (L[,2], NB, bs, Hk, dh); the block axis is ``ndim - 4``.
    ``src``/``dst`` are traced scalars so ONE executable serves every
    copy.
    """
    def cp(c):
        axis = c.ndim - 4
        row = jax.lax.dynamic_index_in_dim(c, src, axis, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(c, row, dst, axis)

    return {name: cp(c) for name, c in cache.items()}


def prefill(cfg: ArchConfig, mesh: Mesh, rules: ShardRules, params, tokens,
            img_embeds=None, *, max_len: int | None = None):
    """Returns (cache {k,v}, last-token logits (B, V))."""
    hidden, _, kv = forward(
        cfg, mesh, rules, params, tokens, img_embeds,
        remat=False, collect_kv=True,
    )
    dec = DecodeSharding.choose(mesh, tokens.shape[0])

    def pad(c):                     # (L[,2], B, S, Hk, dh) -> cache layout
        c = jnp.moveaxis(c, -3, -1)
        if max_len and max_len > c.shape[-1]:
            pad_width = [(0, 0)] * c.ndim
            pad_width[-1] = (0, max_len - c.shape[-1])
            c = jnp.pad(c, pad_width)
        return c

    cache = {"k": pad(kv[0]), "v": pad(kv[1])}
    specs = cache_pspec(cfg, dec)
    from .common import constrain_spec
    cache = {
        name: constrain_spec(c, mesh, specs[name]) for name, c in cache.items()
    }
    logits = unembed(cfg, rules, params, hidden[:, -1])
    return cache, logits


def prefill_slot(cfg: ArchConfig, mesh: Mesh, rules: ShardRules, params,
                 cache, tokens, slot, plen):
    """Prefill ONE prompt into lane ``slot`` of a slotted KV cache.

    tokens: (1, S_bucket) int32 — the prompt, right-padded to its length
    bucket; ``plen`` (traced scalar) is the real prompt length and ``slot``
    (traced scalar) the lane index.  Causality makes the padding inert:
    positions < plen never attend the padded tail, and the tail's garbage
    KV is overwritten by decode steps before the sequence reaches it.

    Returns (cache', logits (1, V) at position plen-1).
    """
    hidden, _, kv = forward(
        cfg, mesh, rules, params, tokens, None, remat=False, collect_kv=True,
    )
    lead = len(_leading(cfg))

    def write(c, new):              # new: (L[,2], 1, S_bucket, Hk, dh)
        new = jnp.moveaxis(new, -3, -1).astype(c.dtype)
        start = (0,) * lead + (slot, 0, 0, 0)
        return jax.lax.dynamic_update_slice(c, new, start)

    cache = {"k": write(cache["k"], kv[0]), "v": write(cache["v"], kv[1])}
    last = jax.lax.dynamic_index_in_dim(hidden, plen - 1, 1, keepdims=False)
    return cache, unembed(cfg, rules, params, last)


def prefill_slot_paged(cfg: ArchConfig, mesh: Mesh, rules: ShardRules, params,
                       cache, tokens, table_row, plen):
    """Prefill the FIRST chunk (positions [0, C)) of one lane into the
    paged pool through its block table.

    Runs the same ``forward`` as :func:`prefill_slot` — activations are
    bitwise-identical, which anchors slotted-vs-paged greedy parity — but
    the collected KV scatters into pool blocks instead of a lane slice.
    tokens: (1, C) right-padded; positions ``>= plen`` divert to the null
    sink block.  Returns (cache', logits (1, V) at ``min(plen, C) - 1``).
    """
    hidden, _, kv = forward(
        cfg, mesh, rules, params, tokens, None, remat=False, collect_kv=True,
    )
    k, v = kv                                   # (L[,2], 1, C, Hk, dh)
    C = tokens.shape[1]
    pos = jnp.arange(C)
    valid = pos < plen

    def write(pool, new):
        flat_pool = pool.reshape((-1,) + pool.shape[-4:])
        new = new.reshape(-1, C, cfg.n_kv, cfg.head_dim)
        out = paged_write_positions(flat_pool, table_row, pos, new, valid)
        return out.reshape(pool.shape)

    cache = {"k": write(cache["k"], k), "v": write(cache["v"], v)}
    last = jax.lax.dynamic_index_in_dim(
        hidden, jnp.clip(plen - 1, 0, C - 1), 1, keepdims=False)
    return cache, unembed(cfg, rules, params, last)


def _block_chunk(cfg, mesh, rules, x, bp, kp, vp, table_row, start, plen, *,
                 window: int):
    """One transformer block of a chunked-prefill continuation.

    x: (1, C, D) hidden for prompt positions [start, start+C); kp/vp:
    block pools (NB, bs, Hk, dh).  Rides ``_block_fwd`` with an attention
    override: write the chunk's K/V through the table, then attend the
    chunk's queries against the lane's gathered KV (previous chunks + the
    chunk itself; the stale tail beyond ``start+C`` is causally masked,
    pad rows never feed valid rows).  Returns (x, kp, vp).
    """
    C = x.shape[1]
    pos = start + jnp.arange(C)

    def attn_fn(q, k, v, w):
        valid = pos < plen
        kp2 = paged_write_positions(kp, table_row, pos, k[0], valid)
        vp2 = paged_write_positions(vp, table_row, pos, v[0], valid)
        kl = paged_gather(kp2, table_row[None])   # (1, S_mapped_view, Hk, dh)
        vl = paged_gather(vp2, table_row[None])
        attn = chunked_attention(
            q, kl, vl,
            causal=True,
            window=w,
            softcap=cfg.attn_softcap,
            q_chunk=min(256, C),
            kv_chunk=min(256, kl.shape[1]),
            q_offset=start,
        )
        return attn, (kp2, vp2)

    x, _, (kp, vp) = _block_fwd(
        cfg, mesh, rules, x, bp, pos[None],
        window=window, collect_kv=False, attn_fn=attn_fn,
    )
    return x, kp, vp


def prefill_chunk_paged(cfg: ArchConfig, mesh: Mesh, rules: ShardRules,
                        params, cache, tokens, table_row, start, plen):
    """Continue a chunked prefill: prompt positions [start, start+C)
    against the lane's existing paged KV (``start > 0``; the first chunk
    goes through :func:`prefill_slot_paged`).

    tokens: (1, C) — the chunk, right-padded on the last chunk; ``start``
    and ``plen`` are traced scalars so ONE executable per chunk size
    serves every continuation.  Returns (cache', logits (1, V) at prompt
    position ``min(plen, start+C) - 1`` — meaningful on the last chunk).
    """
    x = embed_tokens(cfg, rules, params, tokens)          # (1, C, D)
    x = constrain(x, rules, "dp", "sp", None)
    C = tokens.shape[1]
    windows = _windows(cfg)

    def body(i, carry):
        x, kp_all, vp_all = carry
        idx = lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
        bp = jax.tree.map(idx, params["blocks"])
        kp, vp = idx(kp_all), idx(vp_all)
        if len(windows) > 1:
            kps, vps = [], []
            for j, w in enumerate(windows):
                x, kpj, vpj = _block_chunk(
                    cfg, mesh, rules, x, _sub(bp, j), kp[j], vp[j],
                    table_row, start, plen, window=w,
                )
                kps.append(kpj); vps.append(vpj)
            kp, vp = jnp.stack(kps), jnp.stack(vps)
        else:
            x, kp, vp = _block_chunk(
                cfg, mesh, rules, x, bp, kp, vp, table_row, start, plen,
                window=windows[0],
            )
        upd = lambda a, s: jax.lax.dynamic_update_index_in_dim(a, s, i, 0)
        return x, upd(kp_all, kp), upd(vp_all, vp)

    L = jax.tree.leaves(params["blocks"])[0].shape[0]
    x, k_new, v_new = jax.lax.fori_loop(
        0, L, body, (x, cache["k"], cache["v"]))
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    last = jax.lax.dynamic_index_in_dim(
        x, jnp.clip(plen - 1 - start, 0, C - 1), 1, keepdims=False)
    return {"k": k_new, "v": v_new}, unembed(cfg, rules, params, last)


def _layer(a, at):
    """The layer of a stacked cache leaf at leading indices ``at``."""
    n = len(at)
    return jax.lax.dynamic_slice(
        a, tuple(at) + (0,) * (a.ndim - n), (1,) * n + a.shape[n:],
    ).reshape(a.shape[n:])


def _set_layer(a, layer, at):
    """Write ``layer`` back at leading indices ``at`` (in place on a
    donated or loop-carried buffer)."""
    n = len(at)
    return jax.lax.dynamic_update_slice(
        a, layer.reshape((1,) * n + layer.shape), tuple(at) + (0,) * layer.ndim)


def _decode_walk(cfg, mesh, rules, params, cache, x, cur_index, dec, attn_fn):
    """Shared per-layer decode walk for the slotted and paged layouts.

    fori_loop with in-place dynamic updates on the carried cache: the
    stacked KV cache lives in ONE buffer (a scan's xs+ys would
    double-buffer it — 2x HBM for the dominant decode tensor).  Each
    sub-layer's slice goes back to its own place, so nothing restacks
    the (local, global) pair.  The leading layer axes are unsharded, so
    the per-layer slice/update is local (no collectives).
    """
    windows = _windows(cfg)

    def body(i, carry):
        x, kc_all, vc_all = carry
        # each layer's weights plus a zero XLA can neither fold nor hoist:
        # without it XLA converts all layers' weights to the compute dtype
        # ahead of the loop and holds every converted copy as temporaries
        # (629 MB at smollm-360m); with it, one layer's at a time
        zero = jax.lax.optimization_barrier(jnp.float32(0) * i)
        bp = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
            + zero.astype(a.dtype), params["blocks"])
        for j, w in enumerate(windows):
            at = (i, j) if len(windows) > 1 else (i,)
            x, kc, vc = _block_decode(
                cfg, mesh, rules, x, _sub(bp, j) if len(windows) > 1 else bp,
                _layer(kc_all, at), _layer(vc_all, at), cur_index,
                window=w, dec_sharding=dec, attn_fn=attn_fn,
            )
            kc_all = _set_layer(kc_all, kc, at)
            vc_all = _set_layer(vc_all, vc, at)
        return x, kc_all, vc_all

    L = jax.tree.leaves(params["blocks"])[0].shape[0]
    x, k_new, v_new = jax.lax.fori_loop(
        0, L, body, (x, cache["k"], cache["v"]))
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = unembed(cfg, rules, params, x)
    return logits, {"k": k_new, "v": v_new}


def decode_step(cfg: ArchConfig, mesh: Mesh, rules: ShardRules, params, cache,
                tokens, cur_index):
    """tokens: (B,) int32; cur_index: tokens already in cache — a scalar
    (aligned batch) or a (B,) vector (slotted cache, per-lane positions).

    Returns (logits (B, V), new cache).
    """
    x = embed_tokens(cfg, rules, params, tokens[:, None])[:, 0]
    dec = DecodeSharding.choose(mesh, tokens.shape[0])
    return _decode_walk(cfg, mesh, rules, params, cache, x, cur_index, dec, None)


def decode_step_paged(cfg: ArchConfig, mesh: Mesh, rules: ShardRules, params,
                      cache, tokens, lengths, tables, *, impl: str = "ref"):
    """Paged decode: cache leaves are block pools (L[,2], NB, bs, Hk, dh);
    ``tables`` (B, nb) maps each lane's logical blocks to pool rows and
    ``lengths`` (B,) is both the RoPE position and the write position of
    the new token.  ``impl`` picks the attention backend ("ref" jnp
    gather / "pallas" block-walking kernel).

    Returns (logits (B, V), new cache).
    """
    x = embed_tokens(cfg, rules, params, tokens[:, None])[:, 0]

    def attn_fn(q, kc, vc, k_new, v_new, window):
        return paged_decode_attention(
            q, kc, vc, k_new, v_new, lengths, tables,
            window=window, softcap=cfg.attn_softcap, impl=impl,
        )

    return _decode_walk(
        cfg, mesh, rules, params, cache, x, lengths, None, attn_fn)
