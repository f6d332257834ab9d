"""Serving step builders: prefill and decode as separately-jitted programs.

``serve_step`` for the dry-run shapes means: decode shapes lower
``decode_step`` (one new token against a seq_len cache), prefill shapes
lower ``prefill``.

The program builders (``slot_decode_program`` / ``slot_prefill_program``
and their paged twins ``paged_decode_program`` / ``paged_prefill_program``)
are the continuous-batching engine's executables: decode advances every
lane of the cache by one token with sampling **fused on device** (the
host fetches one ``(max_slots,)`` int32 vector per step, not logits —
per-slot temperature/top-k/top-p ride in state vectors), prefill admits
one bucketed prompt — or, paged, one prefill *chunk* — into a lane and
seeds its slot state.  All are plain jitted functions; ``serve/engine.py``
AOT-compiles them through its :class:`~repro.core.aot.AotCache`.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import registry
from repro.models.attention import NEG_INF
from repro.models.common import ShardRules
from repro.train.step import shardings_for
from .faults import NONFINITE_TOKEN, UNCOMMITTED


def jit_prefill(cfg: ArchConfig, mesh: Mesh, rules: ShardRules,
                shape: ShapeConfig, *, max_len: int | None = None):
    mod = registry.get_module(cfg)

    def fn(params, tokens, extra):
        return mod.prefill(cfg, mesh, rules, params, tokens, extra,
                           max_len=max_len)

    params_sds = registry.abstract_params(cfg)
    p_sh = shardings_for(mesh, registry.param_pspecs(cfg, rules))
    in_sds, in_ps = registry.prefill_inputs(cfg, shape, rules)
    tok_sds = in_sds["tokens"]
    tok_sh = NamedSharding(mesh, in_ps["tokens"])
    extra_key = [k for k in in_sds if k != "tokens"]
    if extra_key:
        e_sds = in_sds[extra_key[0]]
        e_sh = NamedSharding(mesh, in_ps[extra_key[0]])
    else:
        e_sds, e_sh = None, None
    jitted = jax.jit(fn, in_shardings=(p_sh, tok_sh, e_sh))
    return jitted, (params_sds, tok_sds, e_sds)


def jit_decode_step(cfg: ArchConfig, mesh: Mesh, rules: ShardRules,
                    shape: ShapeConfig, *, donate: bool = True):
    mod = registry.get_module(cfg)

    def fn(params, cache, tokens, cur_index):
        return mod.decode_step(cfg, mesh, rules, params, cache, tokens, cur_index)

    params_sds = registry.abstract_params(cfg)
    p_sh = shardings_for(mesh, registry.param_pspecs(cfg, rules))
    cache_sds, cache_ps, tok_sds, tok_ps = registry.decode_inputs(cfg, shape, mesh)
    cache_sh = shardings_for(mesh, cache_ps)
    idx_sds = jax.ShapeDtypeStruct((), jnp.int32)
    jitted = jax.jit(
        fn,
        in_shardings=(p_sh, cache_sh, NamedSharding(mesh, tok_ps), None),
        donate_argnums=(1,) if donate else (),
    )
    return jitted, (params_sds, cache_sds, tok_sds, idx_sds)


# ---------------------------------------------------------------------------
# Fused on-device sampling
# ---------------------------------------------------------------------------


def sample_tokens(logits, key, temps, top_k: int = 0, top_ks=None, top_ps=None):
    """Per-row sampling fused into the decode/prefill executables.

    logits: (B, V); temps: (B,) — rows with ``temp == 0`` take the argmax,
    rows with ``temp > 0`` sample ``categorical(logits / temp)``.  Masks,
    all optional and applied only in the stochastic branch:

      top_k    static int — one k for every row (the engine-static knob)
      top_ks   (B,) int32 — per-row k, ``0`` disables that row's mask
      top_ps   (B,) f32   — per-row nucleus threshold applied after
               temperature; ``<= 0`` or ``>= 1`` disables; the most
               probable token always survives

    Returns (B,) int32.  The stochastic branch (PRNG bits + sort-based
    masks over the full (B, V) logits) sits behind a ``lax.cond`` on
    ``any(temp > 0)`` so all-greedy steps pay only the argmax.
    """
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def stochastic(_):
        z = logits
        V = z.shape[-1]
        if top_k:
            kth = jax.lax.top_k(z, top_k)[0][..., -1:]
            z = jnp.where(z < kth, NEG_INF, z)
        safe_t = jnp.where(temps > 0, temps, 1.0)[:, None]
        if top_ks is None and top_ps is None:
            zt = z / safe_t
        else:
            B = z.shape[0]
            ks = jnp.zeros(B, jnp.int32) if top_ks is None else top_ks
            ps = jnp.zeros(B, jnp.float32) if top_ps is None else top_ps

            def masked(zz):
                # ONE argsort serves both per-row masks: the descending
                # sort yields the k-th thresholds directly, and (positive
                # temperature preserving order) the nucleus exclusive
                # cumsum runs over the same permutation
                order = jnp.argsort(-zz, axis=-1)
                z_sorted = jnp.take_along_axis(zz, order, axis=-1)
                kth = jnp.take_along_axis(
                    z_sorted, jnp.clip(ks - 1, 0, V - 1)[:, None], axis=-1)
                drop_k = (ks > 0)[:, None] & (z_sorted < kth)
                p_sorted = jax.nn.softmax(
                    jnp.where(drop_k, NEG_INF, z_sorted) / safe_t, axis=-1)
                # drop tokens whose EXCLUSIVE cumulative probability
                # already reaches p: the smallest set covering p survives,
                # and the top token (exclusive cum = 0) always does
                drop_p = ((ps > 0) & (ps < 1))[:, None] & (
                    jnp.cumsum(p_sorted, axis=-1) - p_sorted >= ps[:, None])
                drop = jnp.take_along_axis(
                    drop_k | drop_p, jnp.argsort(order, axis=-1), axis=-1)
                return jnp.where(drop, NEG_INF, zz / safe_t)

            # all-default steps (no per-row masks anywhere) skip the sort
            need = jnp.any(ks > 0) | jnp.any((ps > 0) & (ps < 1))
            zt = jax.lax.cond(need, masked, lambda zz: zz / safe_t, z)
        sampled = jax.random.categorical(key, zt, axis=-1)
        return jnp.where(temps > 0, sampled.astype(jnp.int32), greedy)

    return jax.lax.cond(jnp.any(temps > 0), stochastic, lambda _: greedy, None)


# ---------------------------------------------------------------------------
# Slot programs (continuous batching)
# ---------------------------------------------------------------------------


def _decode_program(decode_fn, *, eos_id: int | None, freeze=None):
    """Wrap a cache-layout-specific ``decode_fn(params, state) ->
    (logits, cache')`` with the shared scheduling/sampling bookkeeping.

    ``fn(params, state) -> (state', tok)`` — sampling (per-slot
    temperature/top-k/top-p vectors), length bookkeeping, and EOS/budget
    eviction all happen on device; ``tok`` (``(max_slots,)`` int32) is
    the only per-step host fetch.

    ``freeze(cache, active) -> cache`` (recurrent state kinds): applied to
    the post-step cache with the post-step ``active`` vector, zeroing the
    recurrent leaves of inactive lanes — evict-time zeroing fused into
    the decode executable (see :class:`repro.serve.cache.RecurrentCache`).
    """

    def fn(params, state):
        key, sub = jax.random.split(state["key"])
        logits, cache = decode_fn(params, state)
        active = state["active"]
        new_len = state["lengths"] + active.astype(jnp.int32)
        tok = sample_tokens(
            logits, sub, state["temps"],
            top_ks=state["top_ks"], top_ps=state["top_ps"],
        )
        # Non-finite detection rides the SAME (max_slots,) token fetch the
        # host already reads (vocab ids are >= 0, so NONFINITE_TOKEN is
        # unambiguous — no extra sync).  A bad lane is neither finished
        # nor zeroed on device: the host owns the verdict (quarantine +
        # bounded retry through preempt-and-requeue, or terminal failure).
        finite = jnp.all(jnp.isfinite(logits), axis=-1)
        tok = jnp.where(active, tok, 0).astype(jnp.int32)
        tok = jnp.where(active & ~finite, jnp.int32(NONFINITE_TOKEN), tok)
        done = active & finite & (new_len >= state["limits"])
        if eos_id is not None:
            done |= active & (tok == eos_id)
        act_new = active & ~done
        if freeze is not None:
            # a replaying lane's "done" is advisory (the host forces the
            # RECORDED token and may keep the lane alive — e.g. a spurious
            # EOS resampled at a different key position): keep its state
            cache = freeze(cache, act_new | state["replay"])
        new_state = {
            **state, "cache": cache, "tokens": tok, "lengths": new_len,
            "active": act_new, "key": key,
        }
        return new_state, tok

    return fn


def slot_decode_program(cfg: ArchConfig, mesh: Mesh, rules: ShardRules, *,
                        eos_id: int | None = None):
    """One decode step over every lane of the slotted cache.

    Family-generic: ``mod.decode_step`` advances a KV cache (lm), a pure
    per-lane recurrent state (ssm/xlstm — ``lengths`` rides along as the
    logical position but the state is O(1) in it), or zamba's composed
    hybrid cache.  Recurrent leaves of inactive lanes are zeroed on the
    way out (:class:`~repro.serve.cache.RecurrentCache.freeze`)."""
    from .cache import RecurrentCache

    mod = registry.get_module(cfg)
    rec = RecurrentCache(cfg)

    def decode_fn(params, state):
        return mod.decode_step(
            cfg, mesh, rules, params, state["cache"],
            state["tokens"], state["lengths"],
        )

    return _decode_program(decode_fn, eos_id=eos_id,
                           freeze=rec.freeze if rec else None)


def paged_decode_program(cfg: ArchConfig, mesh: Mesh, rules: ShardRules, *,
                         eos_id: int | None = None, impl: str = "ref"):
    """One decode step over every lane of the paged (block-table) cache.
    Identical bookkeeping to :func:`slot_decode_program`; only the cache
    walk differs (``decode_step_paged`` through ``state["tables"]``)."""
    mod = registry.get_module(cfg)

    def decode_fn(params, state):
        return mod.decode_step_paged(
            cfg, mesh, rules, params, state["cache"],
            state["tokens"], state["lengths"], state["tables"], impl=impl,
        )

    return _decode_program(decode_fn, eos_id=eos_id)


def paged_copy_program(cfg: ArchConfig, mesh: Mesh, rules: ShardRules):
    """Copy one physical KV block of the paged pool — the prefix cache's
    copy-on-write step (see :func:`repro.models.lm.copy_paged_block`).

    ``fn(state, src, dst) -> state'`` with ``src``/``dst`` traced scalars:
    one AOT executable serves every COW regardless of which blocks are
    involved.
    """
    mod = registry.get_module(cfg)

    def fn(state, src, dst):
        cache = mod.copy_paged_block(cfg, state["cache"], src, dst)
        return {**state, "cache": cache}

    return fn


def slot_prefill_program(cfg: ArchConfig, mesh: Mesh, rules: ShardRules, *,
                         eos_id: int | None = None):
    """Admit one prompt into lane ``slot``: prefill its KV into the lane
    (prompt padded to a length bucket; one executable per bucket), sample
    the first generated token, and seed the slot's scheduling state
    (including its per-slot sampling params).

    ``fn(params, state, prompt (1, bucket), slot, plen, limit, temp,
    top_k, top_p) -> (state', tok (1,))``, sampling on device.

    Family-generic like :func:`slot_decode_program`: ``mod.prefill_slot``
    writes a KV lane slice (lm), a per-lane recurrent snapshot at
    position ``plen`` (ssm/xlstm), or both (zamba).  Recurrent leaves are
    re-zeroed for inactive lanes on the way out, so a request that
    finishes *at admission* (budget 1 / instant EOS) leaves its lane
    clean.
    """
    from .cache import RecurrentCache

    mod = registry.get_module(cfg)
    rec = RecurrentCache(cfg)

    def fn(params, state, prompt, slot, plen, limit, temp, top_k, top_p):
        key, sub = jax.random.split(state["key"])
        cache, logits = mod.prefill_slot(
            cfg, mesh, rules, params, state["cache"], prompt, slot, plen,
        )
        upd = lambda a, v: a.at[slot].set(jnp.asarray(v).astype(a.dtype))
        new_state = {
            **state,
            "cache": cache,
            "lengths": upd(state["lengths"], plen),
            "limits": upd(state["limits"], limit),
            "temps": upd(state["temps"], temp),
            "top_ks": upd(state["top_ks"], top_k),
            "top_ps": upd(state["top_ps"], top_p),
            "key": key,
        }
        # evict-time zeroing for OTHER lanes only: the slot being prefilled
        # must keep its fresh state even if its sampled token reads as done
        # — a preempted lane's resume forces the RECORDED token host-side
        # and keeps decoding, so zeroing on a (possibly resampled) EOS here
        # would destroy the state the replay is about to advance.  A lane
        # that really finishes at admission is zeroed by the next
        # executable's freeze instead (one-executable lag).  ``replay``
        # lanes are protected here exactly as in the decode program: a
        # mid-replay lane's device ``active`` bit can be stale-False (a
        # spurious EOS the host overrides only at the next sched push,
        # which happens AFTER admissions run), and an admission prefill
        # in that window must not zero the state the replay will advance.
        keep_self = jnp.arange(state["active"].shape[0]) == slot
        keep = state["replay"] | keep_self
        tok = sample_tokens(
            logits, sub, jnp.reshape(temp, (1,)),
            top_ks=jnp.reshape(top_k, (1,)), top_ps=jnp.reshape(top_p, (1,)),
        )
        # non-finite logits report the sentinel token (see _decode_program)
        finite = jnp.all(jnp.isfinite(logits))
        tok = jnp.where(finite, tok, jnp.int32(NONFINITE_TOKEN))
        alive = (plen < limit) & finite
        if eos_id is not None:
            alive &= tok[0] != eos_id
        new_state["tokens"] = upd(state["tokens"], tok[0])
        new_state["active"] = upd(state["active"], alive)
        if rec:
            new_state["cache"] = rec.freeze(
                new_state["cache"], new_state["active"] | keep)
        return new_state, tok

    return fn


def paged_prefill_program(cfg: ArchConfig, mesh: Mesh, rules: ShardRules, *,
                          eos_id: int | None = None, first: bool = True):
    """Process ONE prefill chunk of a request in lane ``slot`` of the
    paged cache — chunked prefill's unit of work, also the whole-prompt
    admission when the chunk is the full bucket.

    ``first=True`` (static): the chunk starts at position 0 and runs the
    plain ``forward`` (bitwise-identical to the slotted prefill) —
    ``start`` is ignored.  ``first=False``: continuation through
    ``prefill_chunk_paged`` at traced offset ``start``.  One executable
    per (chunk size, first?) pair.

    ``fn(params, state, chunk (1, C), slot, start, plen, limit, temp,
    top_k, top_p) -> (state', tok (1,))``.  Scheduling state advances
    every chunk (``lengths`` = prefilled positions); the lane only
    activates — and the returned token is only meaningful — on the chunk
    that covers position ``plen - 1``.
    """
    mod = registry.get_module(cfg)

    def fn(params, state, chunk, slot, start, plen, limit, temp, top_k, top_p):
        key, sub = jax.random.split(state["key"])
        table_row = state["tables"][slot]
        if first:
            cache, logits = mod.prefill_slot_paged(
                cfg, mesh, rules, params, state["cache"], chunk, table_row,
                plen,
            )
            start = jnp.int32(0)
        else:
            cache, logits = mod.prefill_chunk_paged(
                cfg, mesh, rules, params, state["cache"], chunk, table_row,
                start, plen,
            )
        C = chunk.shape[1]
        end = jnp.minimum(start + C, plen)
        is_last = end >= plen
        upd = lambda a, v: a.at[slot].set(jnp.asarray(v).astype(a.dtype))
        new_state = {
            **state,
            "cache": cache,
            "lengths": upd(state["lengths"], end),
            "limits": upd(state["limits"], limit),
            "temps": upd(state["temps"], temp),
            "top_ks": upd(state["top_ks"], top_k),
            "top_ps": upd(state["top_ps"], top_p),
            "key": key,
        }
        tok = sample_tokens(
            logits, sub, jnp.reshape(temp, (1,)),
            top_ks=jnp.reshape(top_k, (1,)), top_ps=jnp.reshape(top_p, (1,)),
        )
        # non-finite logits report the sentinel token (see _decode_program)
        finite = jnp.all(jnp.isfinite(logits))
        tok = jnp.where(finite, tok, jnp.int32(NONFINITE_TOKEN))
        alive = is_last & (plen < limit) & finite
        if eos_id is not None:
            alive &= tok[0] != eos_id
        new_state["tokens"] = upd(
            state["tokens"], jnp.where(is_last, tok[0], state["tokens"][slot]))
        new_state["active"] = upd(state["active"], alive)
        return new_state, tok

    return fn


# ---------------------------------------------------------------------------
# Speculative decoding (draft/verify)
# ---------------------------------------------------------------------------


def spec_decode_program(cfg: ArchConfig, dcfg: ArchConfig, mesh: Mesh,
                        rules: ShardRules, *, k: int,
                        eos_id: int | None = None, paged: bool = False,
                        impl: str = "ref"):
    """One speculative decode round over every lane: the draft model
    proposes ``k`` tokens per lane, the target scores all ``k + 1``
    positions, and each lane commits its accepted prefix — up to ``k + 1``
    tokens per dispatch instead of one.

    ``fn(params, dparams, state) -> (state', rows (max_slots, k+1) int32)``
    — the rows matrix is the ONLY host fetch: entry ``(lane, i)`` is the
    ``i``-th committed token of the lane's round, :data:`UNCOMMITTED`
    past the accepted prefix, or :data:`NONFINITE_TOKEN` for a committed
    position whose logits were non-finite (same quarantine contract as
    the plain decode program).

    Accept rule (greedy path): target step ``i`` consumes input ``u_i``
    (``u_0`` = the lane's pending token, ``u_i = draft_i`` after) at
    position ``lengths + i`` and samples ``y_i``; the chain stays valid
    while ``y_{i-1} == draft_{i-1}``, so every committed ``y_i`` is
    computed from exactly the committed token sequence — bitwise what
    the sequential engine would have sampled, no matter what the draft
    proposed.  The first mismatch commits the *target*'s ``y_i`` (the
    "resample" — for greedy, plain argmax) and invalidates the rest of
    the row.  Stochastic lanes draw per-position subkeys
    (``fold_in(sub, i)``); only the greedy path is bitwise-comparable to
    the sequential engine.

    State handling per kind:

    * **KV (slotted/paged)** — write-then-truncate: rejected positions
      hold junk KV past the commit point, lazily overwritten before the
      lane next attends them (the same argument as eviction; paged junk
      beyond the mapped horizon routes to the write sink, and shared
      prefix blocks are always fully committed so junk never lands in
      one — swept by ``check_invariants``).
    * **recurrent/hybrid leaves** — snapshot/rollback: ``keep`` tracks
      the state after the lane's last *committed* step and is restored
      wholesale on the way out (:meth:`RecurrentCache.rollback`), so a
      rejecting lane's state is bitwise the state before the rejected
      steps ran.

    The draft runs ``k + 1`` steps (the last consumes its own final
    proposal) so its KV covers positions ``lengths .. lengths + k`` —
    no gap when a lane accepts everything.  Recurrent draft leaves
    select the snapshot after step ``c_len - 1``, i.e. having consumed
    exactly the committed sequence minus the new pending token.

    Replaying lanes commit exactly ONE token per round (``valid`` drops
    them after step 0): the host forces each recorded token between
    dispatches, so speculating past the forced token would verify
    against inputs the host is about to override.
    """
    from .cache import RecurrentCache

    if k < 1:
        raise ValueError(f"spec_k must be >= 1, got {k}")
    mod = registry.get_module(cfg)
    dmod = registry.get_module(dcfg)
    rec = RecurrentCache(cfg)
    drec = RecurrentCache(dcfg)

    def target_step(params, state, cache, tok, pos):
        if paged:
            return mod.decode_step_paged(
                cfg, mesh, rules, params, cache, tok, pos,
                state["tables"], impl=impl)
        return mod.decode_step(cfg, mesh, rules, params, cache, tok, pos)

    def fn(params, dparams, state):
        key, sub = jax.random.split(state["key"])
        active = state["active"]
        replay = state["replay"]
        lengths = state["lengths"]
        B = active.shape[0]

        # --- draft pass: k proposals + one covering step ---------------
        dcache = state["draft"]
        drafts, dstates = [], []
        z = state["tokens"]
        for i in range(k + 1):
            dlogits, dcache = dmod.decode_step(
                dcfg, mesh, rules, dparams, dcache, z, lengths + i)
            if drec:
                dstates.append(drec.snapshot(dcache))
            if i < k:
                z = jnp.argmax(
                    dlogits.astype(jnp.float32), axis=-1).astype(jnp.int32)
                drafts.append(z)

        # --- target verify ladder --------------------------------------
        cache = state["cache"]
        keep = rec.snapshot(cache) if rec else None
        valid = active
        c_len = jnp.zeros(B, jnp.int32)
        last_tok = jnp.zeros(B, jnp.int32)
        any_done = jnp.zeros(B, bool)
        rows = []
        u = state["tokens"]
        for i in range(k + 1):
            logits, cache = target_step(params, state, cache, u, lengths + i)
            tok = sample_tokens(
                logits, jax.random.fold_in(sub, i), state["temps"],
                top_ks=state["top_ks"], top_ps=state["top_ps"])
            finite = jnp.all(jnp.isfinite(logits), axis=-1)
            y = jnp.where(finite, tok, jnp.int32(NONFINITE_TOKEN)).astype(
                jnp.int32)
            done = finite & (lengths + i + 1 >= state["limits"])
            if eos_id is not None:
                done |= finite & (y == eos_id)
            committed = valid & finite
            rows.append(jnp.where(valid, y, jnp.int32(UNCOMMITTED)))
            last_tok = jnp.where(committed, y, last_tok)
            c_len = c_len + committed.astype(jnp.int32)
            any_done |= committed & done
            if rec:
                keep = rec.snapshot(rec.rollback(cache, keep, committed))
            if i < k:
                valid = valid & finite & ~done & ~replay & (y == drafts[i])
                u = drafts[i]

        act_new = active & ~any_done
        if rec:
            cache = {**cache, **keep}
            cache = rec.freeze(cache, act_new | replay)
        if drec:
            dsel = dstates[0]
            for j in range(1, k + 1):
                dsel = drec.snapshot(
                    drec.rollback({**dcache, **dstates[j]}, dsel, c_len > j))
            dcache = {**dcache, **dsel}
            dcache = drec.freeze(dcache, act_new | replay)

        new_state = {
            **state, "cache": cache, "draft": dcache,
            "tokens": jnp.where(active, last_tok, 0).astype(jnp.int32),
            "lengths": lengths + c_len,
            "active": act_new, "key": key,
        }
        return new_state, jnp.stack(rows, axis=1)

    return fn


def spec_draft_prefill_program(dcfg: ArchConfig, mesh: Mesh,
                               rules: ShardRules):
    """Seed the DRAFT model's lane from a token history: prefill
    ``hist`` (the prompt plus every committed token except the pending
    one, padded to a bucket) into draft lane ``slot``.

    ``fn(dparams, state, hist (1, bucket), slot, plen) -> state'`` —
    runs at admission and on every restore path (prefix-chain, host-tier,
    held-lane release).  The draft state it builds is *not* bitwise the
    state a decode-origin draft would have — it doesn't need to be:
    committed tokens never depend on draft values, only the accepted
    chain LENGTH does, so rebuilding the draft from history preserves
    output parity exactly.

    Deliberately NO freeze here: the device ``active`` vector can be
    stale mid-admission (the host batches scheduling pushes), so a
    freeze keyed on it could zero a lane another restore seeded moments
    earlier in the same engine step.  Inactive-lane draft zeroing is the
    spec decode program's job — it freezes the draft side every step,
    which is exactly when the invariant sweep checks it.
    """
    dmod = registry.get_module(dcfg)

    def fn(dparams, state, hist, slot, plen):
        dcache, _ = dmod.prefill_slot(
            dcfg, mesh, rules, dparams, state["draft"], hist, slot, plen)
        return {**state, "draft": dcache}

    return fn


# ---------------------------------------------------------------------------
# Host-tier spill/restore transport
# ---------------------------------------------------------------------------
#
# Four fixed-shape programs move lane state between the device cache and
# the host tier.  Shapes are independent of WHICH block/lane moves (the
# index is a traced scalar), so one AOT executable each serves every
# spill and every restore — the transport is builds-flat like the decode
# path, and the steady_builds_delta gates cover tiered modes too.


def paged_block_read_program(cfg: ArchConfig, mesh: Mesh, rules: ShardRules):
    """Read one physical block out of every paged-cache leaf.

    ``fn(state, block) -> {leaf: (bs, Hk, dh)-ish}`` — the replicated
    outputs are fetched to host (``np.asarray``) and become one
    :class:`~repro.serve.paged.LaneSpill` payload block (or a spilled
    prefix block).  Leaves are (L[,2], NB, bs, Hk, dh); the block axis is
    ``ndim - 4`` (see :func:`repro.models.lm.copy_paged_block`).
    """

    def fn(state, block):
        def rd(c):
            return jax.lax.dynamic_index_in_dim(
                c, block, c.ndim - 4, keepdims=False)

        return {name: rd(c) for name, c in state["cache"].items()}

    return fn


def paged_block_write_program(cfg: ArchConfig, mesh: Mesh, rules: ShardRules):
    """Write one physical block of every paged-cache leaf from host
    payloads — the restore half of :func:`paged_block_read_program`.

    ``fn(state, payload, block) -> state'`` with ``payload`` the
    ``{leaf: block}`` tree a spill captured.
    """

    def fn(state, payload, block):
        def wr(c, row):
            return jax.lax.dynamic_update_index_in_dim(
                c, row.astype(c.dtype), block, c.ndim - 4)

        cache = {
            name: wr(c, payload[name]) for name, c in state["cache"].items()
        }
        return {**state, "cache": cache}

    return fn


def lane_read_program(cfg: ArchConfig, mesh: Mesh, rules: ShardRules, *,
                      axes: dict):
    """Read one lane's slice of every slot-cache leaf in ``axes``
    (``registry.lane_leaf_axes``: slotted KV segments and/or recurrent
    leaves — whatever the family says a lane owns).

    ``fn(state, slot) -> {leaf: lane slice}``; outputs are fetched to a
    ``kind == "lane"`` :class:`~repro.serve.paged.LaneSpill`.
    """

    def fn(state, slot):
        return {
            name: jax.lax.dynamic_index_in_dim(
                state["cache"][name], slot, axes[name], keepdims=False)
            for name in axes
        }

    return fn


def lane_write_program(cfg: ArchConfig, mesh: Mesh, rules: ShardRules, *,
                       axes: dict):
    """Write one lane's slice of every slot-cache leaf from host payloads
    — the restore half of :func:`lane_read_program`.

    ``fn(state, payload, slot) -> state'``.  For recurrent leaves this
    must be pushed *with the lane already marked active on device*: the
    prefill program's freeze zeroes inactive lanes, so the engine pushes
    schedule state immediately after a recurrent lane restore.
    """

    def fn(state, payload, slot):
        cache = dict(state["cache"])
        for name, axis in axes.items():
            c = cache[name]
            cache[name] = jax.lax.dynamic_update_index_in_dim(
                c, payload[name].astype(c.dtype), slot, axis)
        return {**state, "cache": cache}

    return fn
