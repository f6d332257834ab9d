"""Slotted cache for the continuous-batching serve engine.

For the KV families the cache is a fixed tensor of ``max_slots`` lanes x
``max_len`` positions (per layer/head as the model family dictates).  A
*slot* is one lane: admission prefills a prompt into a free lane, decode
advances every active lane by one token per step, and eviction just
clears the lane's ``active`` bit — the lane's stale KV is overwritten
lazily (positions are only ever attended at ``pos <= length`` and each
position is rewritten by the decode step before the sequence first
attends it, so garbage left by a previous occupant is never read).

For the *recurrent* state kinds (ssm/xlstm; zamba's mamba leaves) there
is no position axis — each lane's state is O(1) in sequence length, and
the lazy-overwrite argument doesn't apply (decode rewrites the WHOLE
state every step, so an evicted lane's stale state would keep evolving).
:class:`RecurrentCache` manages those leaves: admission hard-resets a
lane (``prefill_slot`` writes the complete state snapshot), and the
decode/prefill programs zero every inactive lane's leaves
(:meth:`RecurrentCache.freeze`), so "inactive lane state == 0" is an
invariant the tests sweep.

All per-slot scheduling state lives **on device** in small vectors so the
decode loop's only host sync is the sampled-token fetch:

    tokens   (N,) int32  last sampled token per slot (next decode input)
    lengths  (N,) int32  tokens currently in the lane's cache
    active   (N,) bool   lane is serving a live request
    limits   (N,) int32  cache length at which the final token is sampled
    temps    (N,) f32    per-slot sampling temperature (0 = greedy)
    top_ks   (N,) int32  per-slot top-k mask (0 = off)
    top_ps   (N,) f32    per-slot nucleus threshold (<=0 or >=1 = off)
    replay   (N,) bool   lane is replaying a preemption resume: its next
                         decode input is host-forced, so on-device "done"
                         verdicts are advisory (recurrent freeze must not
                         zero the lane's state)
    key      PRNG key    split once per engine step (deterministic per seed)

Prompt lengths are **bucketed** (powers of two by default) so one prefill
executable per bucket serves every admission — the AOT dispatch cache
stays flat after warmup instead of compiling per prompt length.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import registry
from repro.models.attention import DecodeSharding

DEFAULT_MIN_BUCKET = 16


def prompt_buckets(max_len: int, min_bucket: int = DEFAULT_MIN_BUCKET) -> tuple[int, ...]:
    """Power-of-two prompt-length buckets, capped at ``max_len``."""
    if max_len < 1:
        raise ValueError(f"max_len must be positive, got {max_len}")
    if min_bucket < 1:
        raise ValueError(f"min_bucket must be positive, got {min_bucket}")
    out: list[int] = []
    b = min(min_bucket, max_len)
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


def bucket_for(plen: int, buckets: tuple[int, ...]) -> int:
    """Smallest bucket that fits a prompt of length ``plen``."""
    if plen < 1:
        raise ValueError(f"prompt length must be positive, got {plen}")
    if not buckets:
        raise ValueError("no prompt buckets configured")
    for b in buckets:
        if b >= plen:
            return b
    raise ValueError(
        f"prompt length {plen} exceeds the largest bucket {buckets[-1]}"
    )


class RecurrentCache:
    """Per-lane recurrent-state manager for the slotted serve engine.

    Wraps :func:`repro.models.registry.recurrent_leaf_axes`: ``leaf_axes``
    maps each recurrent cache leaf (e.g. xlstm's ``m_C`` or zamba's
    ``ssm``) to its lane axis.  Falsy for pure-KV families, so engine and
    program builders can gate on ``if rec:``.

    Lifecycle invariants (asserted in tests/test_serve_engine.py):

    * **admit-time reset** — ``prefill_slot`` overwrites the lane's
      recurrent leaves wholesale with the state snapshot at the prompt
      end; nothing of a previous occupant survives.
    * **evict-time zeroing** — every decode/prefill program passes its
      post-step ``active`` vector through :meth:`freeze`, which zeroes
      the recurrent leaves of every inactive lane *in the same
      executable* (a lane finishing on-device is zeroed in the step that
      finishes it).  So after any such executable, an inactive
      lane's recurrent state is exactly zero — no stale recurrence ever
      advances, and no inf/NaN can accumulate in dead lanes.
    """

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.leaf_axes: dict[str, int] = registry.recurrent_leaf_axes(cfg)

    def __bool__(self) -> bool:
        return bool(self.leaf_axes)

    def _bcast(self, active, leaf, axis: int):
        shape = [1] * leaf.ndim
        shape[axis] = active.shape[0]
        return active.reshape(shape)

    def freeze(self, cache: dict, active) -> dict:
        """Zero the recurrent leaves of every lane whose ``active`` bit is
        False (``(max_slots,)`` bool).  Active lanes pass through bitwise
        (``where`` with a True predicate)."""
        out = dict(cache)
        for name, axis in self.leaf_axes.items():
            leaf = cache[name]
            out[name] = jnp.where(
                self._bcast(active, leaf, axis), leaf,
                jnp.zeros((), leaf.dtype))
        return out

    def snapshot(self, cache: dict) -> dict:
        """The recurrent leaves of ``cache`` as a flat dict — a cheap
        per-lane-state copy (XLA aliases the arrays; a later ``where``
        against the snapshot is the only materialization).  Used by the
        speculative verify program to roll lanes back to their last
        committed step."""
        return {name: cache[name] for name in self.leaf_axes}

    def rollback(self, cache: dict, snap: dict, keep) -> dict:
        """Per-lane select between ``cache`` (lanes where ``keep`` is
        True) and the earlier ``snapshot`` ``snap`` (lanes where it is
        False).  ``keep`` is ``(max_slots,)`` bool.  Kept lanes pass
        through bitwise (``where`` with a True predicate), so a lane that
        accepted every speculative token is untouched and a lane that
        rejected is bitwise the state it had before the rejected steps
        ran — the property tests/test_spec_decode.py pins."""
        out = dict(cache)
        for name, axis in self.leaf_axes.items():
            out[name] = jnp.where(
                self._bcast(keep, cache[name], axis), cache[name],
                snap[name])
        return out

    def lane_is_zero(self, cache: dict, slot: int) -> bool:
        """Host-side check: lane ``slot``'s recurrent leaves are all
        exactly zero (the evict-time-zeroing invariant)."""
        return self.lanes_are_zero(cache, [slot])

    def lanes_are_zero(self, cache: dict, slots) -> bool:
        """``lane_is_zero`` over several lanes with ONE host fetch per
        leaf (the invariant sweep runs after every fuzzer step — per-lane
        fetches of whole leaves would multiply transfers)."""
        slots = list(slots)
        if not slots:
            return True
        for name, axis in self.leaf_axes.items():
            lanes = np.take(np.asarray(cache[name]), slots, axis=axis)
            if np.any(lanes != 0):
                return False
        return True


def sched_specs(mesh, max_slots: int):
    """Per-slot scheduling vectors shared by the slotted and paged layouts:
    ``({leaf: sds}, {leaf: NamedSharding})`` (all replicated)."""
    rep = NamedSharding(mesh, P())
    n = max_slots
    sds = {
        "tokens": jax.ShapeDtypeStruct((n,), jnp.int32),
        "lengths": jax.ShapeDtypeStruct((n,), jnp.int32),
        "active": jax.ShapeDtypeStruct((n,), jnp.bool_),
        "limits": jax.ShapeDtypeStruct((n,), jnp.int32),
        "temps": jax.ShapeDtypeStruct((n,), jnp.float32),
        "top_ks": jax.ShapeDtypeStruct((n,), jnp.int32),
        "top_ps": jax.ShapeDtypeStruct((n,), jnp.float32),
        "replay": jax.ShapeDtypeStruct((n,), jnp.bool_),
        "key": jax.ShapeDtypeStruct((2,), jnp.uint32),
    }
    sh = {k: rep for k in sds}
    return sds, sh


def slot_state_specs(cfg: ArchConfig, mesh, max_slots: int, max_len: int):
    """Abstract slot state: ``({leaf: sds}, {leaf: NamedSharding})``."""
    mod = registry.get_module(cfg)
    dec = DecodeSharding.choose(mesh, max_slots)
    cache_sds = mod.make_cache_specs(cfg, max_slots, max_len)
    cache_ps = mod.cache_pspec(cfg, dec)
    sched_sds, sched_sh = sched_specs(mesh, max_slots)
    sds = {"cache": cache_sds, **sched_sds}
    sh = {
        "cache": jax.tree.map(
            lambda p: NamedSharding(mesh, p), cache_ps,
            is_leaf=lambda x: isinstance(x, P),
        ),
        **sched_sh,
    }
    return sds, sh


def make_slot_state(cfg: ArchConfig, mesh, max_slots: int, max_len: int,
                    seed: int = 0) -> dict:
    """Allocate the device-resident slot state (all lanes free)."""
    sds, sh = slot_state_specs(cfg, mesh, max_slots, max_len)
    state = jax.tree.map(
        lambda s, d: jax.device_put(jnp.zeros(s.shape, s.dtype), d), sds, sh,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )
    state["key"] = jax.device_put(
        jax.random.PRNGKey(seed).astype(jnp.uint32), sh["key"]
    )
    return state


def state_sds(state) -> dict:
    """ShapeDtypeStructs of a live state tree (for AOT lowering)."""
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
