"""Continuous-batching serve engine over slotted or paged per-lane state.

The engine runs one fixed-shape decode executable over ``max_slots`` cache
lanes.  Requests are admitted into free lanes at *any* decode step (prefill
through a length-bucketed executable), finished sequences are evicted
immediately (EOS or token budget), and sampling is fused into the decode
program — the per-step host sync is a single ``(max_slots,)`` int32 token
fetch instead of a logits round-trip.

"Per-lane decode state" is an abstraction, not a KV assumption
(``registry.state_kind``): the lm families carry a seq-axis KV cache,
``ssm``/xlstm carry pure per-lane recurrent state (O(1) in sequence
length — admission hard-resets a lane, eviction zeroes it), and zamba's
``hybrid`` lanes compose BOTH kinds in one cache dict (a slotted KV
segment for the shared attention block next to recurrent mamba leaves).
Admission, eviction, preempt-and-requeue, and ``prebuild()`` are
state-kind-agnostic; only the paged layout below is KV-only (recurrent
state has no seq axis to page).

Two cache layouts (``EngineConfig.kv_layout``):

``slotted``  fixed ``max_slots x max_len`` lanes — every lane reserves
             worst-case HBM (the PR-2 baseline, kept for parity).
``paged``    a shared pool of fixed-size KV blocks with per-lane block
             tables (serve/paged.py): blocks are allocated on demand —
             prompt blocks at admission, one more each time decode
             crosses a block boundary — and freed on eviction, so
             reservation is ``num_blocks * page_size`` positions sized to
             load, not ``max_slots * max_len``.  Greedy decoding is
             token-for-token identical to the slotted layout (asserted in
             tests and the cross-engine fuzzer).

On the paged layout, **chunked prefill** (``EngineConfig.prefill_chunk``)
admits long prompts as fixed-size chunks processed one per engine step and
interleaved with decode, instead of one monolithic prefill call blocking
the whole batch; one AOT executable per chunk size serves every prompt.

Every executable is AOT-compiled once per static key through an
:class:`~repro.core.aot.AotCache`, so steady-state dispatch is a dict
probe: after warmup the engine's ``builds`` counter must stay flat
(asserted by ``tests/test_paged.py::test_engine_steady_builds_flat`` for
both layouts).

Host-side the engine keeps a mirror of the scheduling vectors (lengths,
budgets, block tables, which request owns which lane).  The mirror is
advanced by the same rules the device applies, so the engine never reads
device state back except the sampled tokens it needs to stream anyway;
block accounting is pure host bookkeeping plus a tiny ``tables`` re-push
whenever a row changes.

    engine = ServeEngine(cfg, mesh, rules, params,
                         EngineConfig(max_slots=8, max_len=256,
                                      kv_layout="paged", prefill_chunk=32))
    rid = engine.submit(prompt_ids, max_new_tokens=32, temperature=0.7,
                        top_k=50, top_p=0.9)
    engine.drain()                       # or step() under an arrival loop
    out = engine.completions[rid].tokens
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import time
from collections import deque
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.aot import AotCache
from repro.models import registry
from repro.models.attention import DecodeSharding
from repro.obs import MetricMap, Observer
from repro.models.common import ShardRules
from repro.train.step import shardings_for
from .faults import NONFINITE_TOKEN, UNCOMMITTED, FaultPlan
from .cache import (
    RecurrentCache,
    bucket_for,
    make_slot_state,
    prompt_buckets,
    slot_state_specs,
    state_sds,
)
from .paged import (
    BlockAllocator,
    HostTier,
    LaneSpill,
    SlotTables,
    blocks_for,
    cache_nbytes,
    check_tiered,
    make_paged_state,
    paged_state_specs,
    prefix_keys,
)
from .step import (
    lane_read_program,
    lane_write_program,
    paged_block_read_program,
    paged_block_write_program,
    paged_copy_program,
    paged_decode_program,
    paged_prefill_program,
    slot_decode_program,
    slot_prefill_program,
    spec_decode_program,
    spec_draft_prefill_program,
)


def _exact_share(total: int, units: int, denom: int) -> int:
    """``units``/``denom`` of ``total`` bytes, multiplied BEFORE dividing.

    The historical per-unit form ``(total // denom) * units`` truncates
    the per-unit share on non-divisible shapes and under-reports when
    scaled back up (by up to ``units * (denom - 1)`` bytes); multiplying
    first keeps the result the exact floor of the true fraction — and
    exact, full stop, whenever ``denom`` is an axis factor of every leaf
    (the common case for block/lane/position counts)."""
    return total * units // denom


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static engine configuration.

    Fields that change a *lowered program* (shape/layout/spec draft)
    are folded into the AOT cache key (``ServeEngine._sampler_key``), so
    engines differing there never share executables; host-side policies
    (``prefix_cache``, ``admission``) deliberately are not — they reuse
    the same compiled programs.  Layout fields (``kv_layout`` and below)
    apply to the ``"kv"`` state kind only; recurrent/hybrid families
    serve on the slotted layout and reject paged-only options with a
    ``ValueError`` at construction.
    """

    max_slots: int = 8            # cache lanes decoded per step
    max_len: int = 256            # max per-lane sequence length
    eos_id: int | None = None     # None: budget-only eviction
    top_k: int = 0                # default per-request top-k (0 = off)
    top_p: float = 0.0            # default per-request nucleus p (off)
    seed: int = 0
    # prompt-length buckets for the prefill executables; None -> powers of
    # two up to max_len (one AOT build per bucket ever used)
    prefill_buckets: tuple[int, ...] | None = None
    # --- KV layout -----------------------------------------------------
    kv_layout: str = "slotted"    # "slotted" | "paged"
    page_size: int = 16           # KV block size (paged)
    # pool size in blocks incl. the null block; None -> worst case
    # (max_slots * max_len/page_size + 1) — size it below that to reserve
    # less HBM than the slotted layout
    num_blocks: int | None = None
    # >0: admit prompts in chunks of this many tokens, one chunk per
    # engine step, interleaved with decode (paged only; 0 = whole-prompt
    # bucketed prefill)
    prefill_chunk: int = 0
    paged_attn: str = "ref"       # paged decode backend: "ref" | "pallas"
    # paged only: refcounted shared-prefix block reuse — submit matches a
    # new prompt against the published-block index and only prefills the
    # unmatched suffix (COW on the partial tail block)
    prefix_cache: bool = False
    # "deficit": admission gated on worst-case block commitments (decode
    # growth can never exhaust the pool).  "preempt": admit on immediate
    # need only; when growth finds the pool empty, evict the lowest-
    # priority lane back to the queue (tokens + sampling state requeued,
    # table nulled, refs dropped) — the pool runs near full
    admission: str = "deficit"
    # bounded retry budget per request: how many times a faulted lane
    # (non-finite logits, failed prefill dispatch, failed block alloc) is
    # quarantined and requeued through the preempt-and-requeue path
    # before the request goes terminal with status "failed"
    max_retries: int = 2
    # --- host-RAM tier (any layout / state kind) -----------------------
    # spill a preempted lane's state (KV blocks, or the whole-lane
    # slice registry.lane_leaf_axes describes) to host RAM and restore
    # it O(copy) at resume instead of O(generated-tokens) decode replay;
    # LRU-reclaimed prefix-cache blocks also spill (the tier is a
    # second-level prefix cache), and hold()-idle lanes can park
    # off-HBM.  Host-side policy: does NOT change executable keys beyond
    # adding the prebuilt transport programs.
    host_tier: bool = False
    # host pool budget in KV-block-sized units (paged payloads; see
    # HostTier); None = unbounded.  Ignored when the caller passes a
    # shared HostTier instance (the router does, fleet-wide).
    host_tier_blocks: int | None = None
    # a held lane parks off-HBM (lane freed, state host-resident) after
    # being held this many clock-seconds; None = held lanes stay
    # resident until release()
    park_idle_s: float | None = None
    # --- speculative decoding (any layout / state kind) ----------------
    # draft model config (an ArchConfig from models/registry): each
    # engine step the draft proposes ``spec_k`` greedy tokens per lane
    # and ONE bucketed verify executable scores all k+1 positions with
    # the target — accepted prefixes commit, the first rejection
    # resamples from the target distribution.  Greedy verification is a
    # plain argmax comparison, so the committed stream is bitwise the
    # sequential engine's (asserted by the fuzzer across every state
    # kind).  Both fields set together.
    spec_draft: Any = None
    spec_k: int = 0


@dataclasses.dataclass
class _Slot:
    rid: int
    plen: int
    limit: int                    # cache length at which the last token samples
    temperature: float
    top_k: int
    top_p: float
    prompt: np.ndarray
    chunk: int                    # prefill chunk size (== bucket when whole)
    prefilled: int = 0            # prompt positions prefilled so far
    generated: int = 0
    pub_upto: int = 0             # leading blocks already published/matched
    emit_from: int = 0            # first k generated tokens are a replay
    #                               of already-emitted output: not re-appended
    hasher: Any = None            # incremental chain hash (prefix_keys
    hashed: int = 0               # equivalent); blocks digested so far
    deadline: float | None = None # absolute clock() time the request expires
    held: bool = False            # hold(): lane paused between user turns
    held_since: float | None = None  # clock() of the hold (park threshold)


# Terminal per-request statuses (Completion.status).  Failures are data,
# not exceptions: step() never raises for a request-level fault.  "shed"
# is produced only by the router front-end (load shedding rejects a
# request before any engine ever holds it), but it lives in the shared
# vocabulary so Completion consumers handle one status set.
STATUSES = ("ok", "timeout", "cancelled", "failed", "shed")


@dataclasses.dataclass
class Completion:
    rid: int
    prompt_len: int
    max_new_tokens: int
    tokens: list[int]
    token_times: list[float]      # clock() when each token reached the host
    submit_time: float
    finish_time: float
    # "ok" | "timeout" | "cancelled" | "failed" — non-ok completions hold
    # the tokens emitted before termination (a prefix of the fault-free
    # stream under greedy decoding)
    status: str = "ok"
    error: str | None = None      # terminal failure reason (status "failed")
    retries: int = 0              # fault retries consumed (quarantine count)


@dataclasses.dataclass
class _Pending:
    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    temperature: float
    top_k: int
    top_p: float
    submit_time: float
    deadline: float | None = None # absolute expiry (submit_time + deadline_s)
    # preempt-and-requeue: a preempted lane requeues with its ORIGINAL
    # prompt plus the tokens already emitted (``replay``).  On
    # re-admission the prompt prefills as usual (prefill-origin KV is
    # bitwise chunk-invariant) and the generated tokens REGENERATE through
    # the decode path — decode-origin positions are only ever recomputed
    # by decode, never by prefill, so the resumed stream is bitwise the
    # original on any mesh/dtype (re-prefilling them is NOT bitwise-stable
    # under sharded bf16 reductions).  Replayed tokens are suppressed from
    # the output; a prefix-cache hit on the lane's own published chain
    # skips the replay entirely (restored mid-decode).  ``limit`` pins the
    # original budget; the live Completion is kept.
    resume: bool = False
    limit: int = 0
    replay: tuple[int, ...] = ()
    # set when the lane preempted ITSELF growing to this many blocks:
    # don't re-admit until the pool can plausibly cover that need, else
    # the same prefill chunks recompute every step until someone frees
    min_free: int = 0


class ServeEngine:
    """Continuous-batching serve engine (see the module docstring).

    Core invariants (swept by :meth:`check_invariants` and the fuzzer):

    * Slot conservation: ``admitted - evicted == len(live)`` == occupied
      lanes; a lane is owned by at most one request.
    * Paged block conservation: ``free + live + cached == capacity`` in
      the :class:`~repro.serve.paged.BlockAllocator`; every mapped block's
      refcount covers its mapping multiplicity; every written KV position
      lies inside its lane's mapped region.
    * Dispatch flatness: after :meth:`prebuild`, the AOT ``builds``
      counter never grows (asserted by the tier-1 tests).
    * Recurrent zeroing (non-``kv`` state kinds): an
      inactive lane's recurrent leaves are exactly zero after the next
      executable runs — admission hard-resets, eviction zeroes.
    * Host mirror coherence: the scheduling vectors the host keeps are
      advanced by the same rules the device applies; the only per-step
      device read is the sampled-token fetch.
    """

    def __init__(
        self,
        cfg: ArchConfig,
        mesh,
        rules: ShardRules,
        params,
        engine: EngineConfig = EngineConfig(),  # noqa: B008 - frozen, never mutated
        *,
        aot: AotCache | None = None,
        clock: Callable[[], float] = time.perf_counter,
        faults: FaultPlan | None = None,
        obs: Observer | None = None,
        host_tier: HostTier | None = None,
        draft_params=None,
    ):
        if not registry.supports_slot_serving(cfg):
            raise ValueError(
                f"family {cfg.family!r} does not support slot serving; "
                "use serve.loop.generate_static"
            )
        if engine.kv_layout not in ("slotted", "paged"):
            raise ValueError(f"unknown kv_layout {engine.kv_layout!r}")
        self.kind = registry.state_kind(cfg)
        self.rec = RecurrentCache(cfg)
        self.paged = engine.kv_layout == "paged"
        if not self.paged and engine.prefill_chunk:
            raise ValueError("prefill_chunk requires kv_layout='paged'")
        if engine.admission not in ("deficit", "preempt"):
            raise ValueError(f"unknown admission {engine.admission!r}")
        if not self.paged and engine.prefix_cache:
            raise ValueError("prefix_cache requires kv_layout='paged'")
        if not self.paged and engine.admission != "deficit":
            raise ValueError("admission='preempt' requires kv_layout='paged'")
        if self.paged and not registry.supports_paged_serving(cfg):
            if self.kind != "kv":
                raise ValueError(
                    f"family {cfg.family!r} has state kind {self.kind!r}: "
                    "per-lane recurrent state is O(1) in sequence length — "
                    "there is no seq axis to page; use kv_layout='slotted'")
            raise ValueError(
                f"family {cfg.family!r} does not support paged serving")
        self.spec = engine.spec_draft is not None
        if self.spec != (engine.spec_k > 0):
            raise ValueError(
                "spec_draft and spec_k must be set together "
                f"(spec_draft={engine.spec_draft!r}, spec_k={engine.spec_k})")
        if self.spec:
            if not registry.supports_slot_serving(engine.spec_draft):
                raise ValueError(
                    f"draft family {engine.spec_draft.family!r} does not "
                    "support slot serving")
            if engine.spec_draft.vocab != cfg.vocab:
                raise ValueError(
                    f"draft vocab {engine.spec_draft.vocab} != target "
                    f"vocab {cfg.vocab}: verify compares token ids")
        self.cfg, self.mesh, self.rules = cfg, mesh, rules
        self.econ = engine
        self.buckets = tuple(engine.prefill_buckets or prompt_buckets(engine.max_len))
        if max(self.buckets) > engine.max_len:
            raise ValueError("prefill bucket exceeds max_len")
        # Observability (repro.obs): metrics are always live (typed
        # counters behind the legacy ``self.counters`` mapping shape);
        # tracing and the flight recorder only run when the caller's
        # Observer carries them — every emit is behind an ``is not None``
        # so a default engine pays one attribute check, no host syncs,
        # and no executable-key changes.
        self.obs = obs if obs is not None else Observer(name="engine")
        self._track = self.obs.name
        # NOT ``aot or ...``: AotCache defines __len__, so a freshly made
        # (empty) shared cache is falsy and would be silently replaced —
        # every caller would then compile privately
        self.aot = aot if aot is not None else AotCache("serve", obs=self.obs)
        self.clock = clock
        # deterministic fault injection (serve/faults.py); None = off, and
        # every consult site is behind an ``is not None`` so the default
        # engine pays nothing
        self.faults = faults

        self._p_sh = shardings_for(mesh, registry.param_pspecs(cfg, rules))
        self._rep = NamedSharding(mesh, P())
        self.params = jax.device_put(params, self._p_sh)
        self._params_sds = registry.abstract_params(cfg)
        if self.paged:
            bs = engine.page_size
            if engine.max_len % bs:
                raise ValueError(
                    f"max_len ({engine.max_len}) must be a multiple of "
                    f"page_size ({bs})"
                )
            blocks_per_slot = engine.max_len // bs
            want = engine.num_blocks or engine.max_slots * blocks_per_slot + 1
            # round the pool up to the data-parallel size so its block dim
            # shards evenly — per-DEVICE reservation then scales down with
            # DP like the slotted cache's batch-sharded lanes does
            ndp = int(np.prod([
                mesh.shape[a] for a in ("pod", "data")
                if a in mesh.axis_names
            ]))
            self._num_blocks = -(-want // max(ndp, 1)) * max(ndp, 1)
            self.alloc = BlockAllocator(self._num_blocks, bs)
            self.tables = SlotTables(engine.max_slots, blocks_per_slot)
            self._deficit = 0           # committed-but-unallocated blocks
            self._slot_wc = [0] * engine.max_slots
            self._tables_dirty = False
            _, self._state_sh = paged_state_specs(
                cfg, mesh, engine.max_slots, engine.max_len,
                self._num_blocks, bs)
            self.state = make_paged_state(
                cfg, mesh, engine.max_slots, engine.max_len,
                self._num_blocks, bs, engine.seed)
        else:
            self._num_blocks = 0
            _, self._state_sh = slot_state_specs(
                cfg, mesh, engine.max_slots, engine.max_len)
            self.state = make_slot_state(
                cfg, mesh, engine.max_slots, engine.max_len, engine.seed)
        # --- speculative-decode draft lane state -----------------------
        # the draft cache is ALWAYS slotted (even under a paged target):
        # its per-lane state is small — max_slots x max_len worst-case for
        # a KV draft, O(1) for a recurrent one — and lives as one more
        # leaf of the engine state dict so the verify executable advances
        # target and draft in a single dispatch.  Draft state is never
        # spilled to the host tier: committed tokens fully determine it,
        # so restores rebuild it with one draft prefill over the history
        # (greedy parity is draft-independent — drafts only gate how many
        # target tokens commit per step, never their values).
        self._draft_rec = None
        self.draft_params = None
        if self.spec:
            dcfg = engine.spec_draft
            dmod = registry.get_module(dcfg)
            ddec = DecodeSharding.choose(mesh, engine.max_slots)
            dsds = dmod.make_cache_specs(dcfg, engine.max_slots,
                                         engine.max_len)
            dsh = jax.tree.map(
                lambda p: NamedSharding(mesh, p), dmod.cache_pspec(dcfg, ddec),
                is_leaf=lambda x: isinstance(x, P))
            self._state_sh["draft"] = dsh
            self.state["draft"] = jax.tree.map(
                lambda sd, d: jax.device_put(jnp.zeros(sd.shape, sd.dtype), d),
                dsds, dsh,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
            self._dp_sh = shardings_for(mesh, registry.param_pspecs(dcfg, rules))
            self._dparams_sds = registry.abstract_params(dcfg)
            if draft_params is None:
                # self-contained default (router replicas need zero extra
                # plumbing): a deterministic draft init from the engine
                # seed.  Real deployments pass trained draft weights.
                draft_params = dmod.init(dcfg, jax.random.PRNGKey(engine.seed))
            self.draft_params = jax.device_put(draft_params, self._dp_sh)
            self._draft_rec = RecurrentCache(dcfg)
            # draft rebuilds cover committed HISTORIES (prompt + emitted
            # tokens), which can outgrow the largest *prompt* bucket when
            # prefill_buckets is customized below max_len
            self._spec_buckets = self.buckets \
                if max(self.buckets) >= engine.max_len \
                else self.buckets + (engine.max_len,)
        elif draft_params is not None:
            raise ValueError(
                "draft_params passed but EngineConfig.spec_draft is None")
        self._state_sds = state_sds(self.state)
        self.kv_reserved_bytes = cache_nbytes(self.state["cache"])
        # what the lanes hold, by lifecycle: the KV segment and the
        # recurrent leaves (registry.recurrent_leaf_axes)
        rec_bytes = cache_nbytes([self.state["cache"][n]
                                  for n in self.rec.leaf_axes])
        self.obs.metrics.gauge("engine.kv_bytes").set(
            self.kv_reserved_bytes - rec_bytes)
        self.obs.metrics.gauge("engine.recurrent_bytes").set(rec_bytes)

        # --- host-RAM tier ---------------------------------------------
        # paged engines spill per-block (the lane's KV, block by block);
        # slotted engines spill the whole-lane slice the family declares
        self._lane_axes = {} if self.paged else registry.lane_leaf_axes(cfg)
        if engine.park_idle_s is not None and not engine.host_tier:
            raise ValueError("park_idle_s requires host_tier=True")
        if engine.host_tier_blocks is not None and not engine.host_tier:
            raise ValueError("host_tier_blocks requires host_tier=True")
        if engine.host_tier:
            if not self.paged and not self._lane_axes:
                raise ValueError(
                    f"family {cfg.family!r} declares no lane_leaf_axes — "
                    "the host tier has nothing to spill on the slotted "
                    "layout")
            # a caller-provided tier is SHARED (the router passes one per
            # fleet so spills survive replica crashes)
            self.tier = host_tier if host_tier is not None \
                else HostTier(engine.host_tier_blocks)
            if self.paged and engine.prefix_cache:
                self.alloc.on_evict = self._spill_block
        else:
            if host_tier is not None:
                raise ValueError(
                    "host_tier instance passed but EngineConfig.host_tier "
                    "is False")
            self.tier = None
        # rid -> pending resume for lanes parked off-HBM by hold() +
        # park_idle_s; re-enters the queue on release()
        self.parked: dict[int, _Pending] = {}

        self.queue: deque[_Pending] = deque()
        self.slots: list[_Slot | None] = [None] * engine.max_slots
        self.live: dict[int, Completion] = {}
        self.completions: dict[int, Completion] = {}
        # the historical dict shape, now backed by typed metrics:
        # ``kv_peak_used_bytes`` is a Gauge (peak set, not a sum — see
        # _note_kv_usage); everything else is a monotone Counter.  The
        # kind split is asserted by check_invariants.
        self.counters = MetricMap(self.obs.metrics, (
            "prefills", "prefill_chunks", "decode_steps",
            "admitted", "evicted", "dead_slot_steps",
            "kv_peak_used_bytes", "prefill_tokens",
            "prefix_lookup_tokens", "prefix_hit_tokens",
            "cow_copies", "preemptions", "resumed",
            "replayed_tokens",
            # fault-tolerance lifecycle
            "status_ok", "status_timeout", "status_cancelled",
            "status_failed", "status_shed", "retries",
            "faults_injected", "faults_detected",
            "snapshot_restores",
            # per-request migration (router failover / drain)
            "exported", "imported",
            # host-RAM tier: lane spills/restores (O(copy) resume),
            # spilled prefix blocks, bytes moved each way, payloads the
            # tier refused (replay fallback), and hold/park lifecycle
            "spills", "restores", "spilled_bytes", "restored_bytes",
            "spill_drops", "prefix_spills", "host_prefix_hits",
            "holds", "releases", "parked",
            # speculative decoding: verify dispatches, lane-rounds (one
            # active lane in one verify dispatch), draft tokens
            # proposed/accepted, explicit rejections, and total committed
            # tokens (spec_committed / spec_rounds = mean committed chain
            # length per lane-round — the sequential engine is exactly
            # 1.0, so > 1.0 is the headline speedup)
            "spec_steps", "spec_rounds", "spec_drafted", "spec_accepted",
            "spec_rejected", "spec_committed",
        ), gauges=("kv_peak_used_bytes",))
        self._kv_gauge = self.obs.metrics.gauge("kv_peak_used_bytes")
        self._next_rid = 0
        # lanes barred from admission for this many more steps after a
        # fault (quarantine): the faulted occupant has already requeued,
        # and one cooldown step keeps a hot fault site from re-admitting
        # into the same lane within the same engine step
        self._quarantine = [0] * engine.max_slots
        # deadline sweep is O(queue + slots) per step; skip it entirely
        # until some request actually carries a deadline
        self._has_deadlines = False
        self._tok_mirror = np.zeros(engine.max_slots, np.int32)
        self._active_mirror = np.zeros(engine.max_slots, bool)
        self._active_dirty = False
        self._sched_dirty = False
        # lanes whose NEXT decode input is a host-forced replay token: the
        # device's done verdict is advisory there, and the recurrent
        # freeze must not zero the lane's state (see serve/step.py)
        self._replay_mirror = np.zeros(engine.max_slots, bool)
        # last engine operation ("prefill" | "decode" | "preempt") — the
        # recurrent zeroing invariant is only checkable right after a
        # decode (host-side evictions zero one executable later)
        self._last_op: str | None = None

    # ------------------------------------------------------------------
    # Executables (AOT via the shared cache)
    # ------------------------------------------------------------------
    def _sampler_key(self) -> tuple:
        e = self.econ
        return (self.cfg.name, e.max_slots, e.max_len, e.eos_id,
                e.kv_layout, e.page_size,
                self._num_blocks, e.paged_attn,
                # spec changes the STATE SHAPE (the draft leaf), so every
                # executable — not just the verify program — keys on it
                e.spec_draft.name if e.spec_draft else None, e.spec_k)

    def _decode_exe(self):
        key = ("slot_decode",) + self._sampler_key()

        def build():
            e = self.econ
            if self.paged:
                fn = paged_decode_program(
                    self.cfg, self.mesh, self.rules, eos_id=e.eos_id,
                    impl=e.paged_attn,
                )
            else:
                fn = slot_decode_program(
                    self.cfg, self.mesh, self.rules, eos_id=e.eos_id,
                )
            jitted = jax.jit(
                fn, in_shardings=(self._p_sh, self._state_sh),
                # pin state outputs to the canonical shardings so decode
                # and prefill executables hand the state back and forth
                # without resharding (AOT calls check shardings exactly)
                out_shardings=(self._state_sh, self._rep),
                donate_argnums=(1,),
            )
            exe = jitted.lower(self._params_sds, self._state_sds).compile()
            # the step's scratch beside the donated state: what a cache
            # re-laid out or restacked every step would hold
            self.obs.metrics.gauge("engine.decode_temp_bytes").set(
                exe.memory_analysis().temp_size_in_bytes)
            return exe

        return self.aot.get(key, build)

    def _spec_exe(self):
        """The draft+verify speculative step (serve/step.py): k greedy
        draft proposals plus k+1 target scores per lane in ONE dispatch,
        returning a ``(max_slots, k+1)`` row matrix — still a single
        int32 fetch per engine step."""
        key = ("spec_decode",) + self._sampler_key()

        def build():
            e = self.econ
            fn = spec_decode_program(
                self.cfg, e.spec_draft, self.mesh, self.rules, k=e.spec_k,
                eos_id=e.eos_id, paged=self.paged, impl=e.paged_attn,
            )
            jitted = jax.jit(
                fn,
                in_shardings=(self._p_sh, self._dp_sh, self._state_sh),
                out_shardings=(self._state_sh, self._rep),
                donate_argnums=(2,),
            )
            return jitted.lower(self._params_sds, self._dparams_sds,
                                self._state_sds).compile()

        return self.aot.get(key, build)

    def _spec_prefill_exe(self, bucket: int):
        """Rebuild one lane's draft cache from its committed token
        history (admission, and every restore path — draft state is
        never spilled)."""
        key = ("spec_draft_prefill", bucket) + self._sampler_key()

        def build():
            rep = self._rep
            i32 = lambda shape=(): jax.ShapeDtypeStruct(shape, jnp.int32)
            fn = spec_draft_prefill_program(
                self.econ.spec_draft, self.mesh, self.rules)
            jitted = jax.jit(
                fn,
                in_shardings=(self._dp_sh, self._state_sh, rep, rep, rep),
                out_shardings=self._state_sh,
                donate_argnums=(1,),
            )
            return jitted.lower(self._dparams_sds, self._state_sds,
                                i32((1, bucket)), i32(), i32()).compile()

        return self.aot.get(key, build)

    def _spec_draft_prefill(self, slot: int, hist: np.ndarray) -> None:
        """Seed lane ``slot``'s draft cache with the committed history
        ``hist`` (prompt, or prompt + committed tokens up to — not
        including — the pending decode input).  Bucketed like the target
        prefill so the AOT cache stays flat."""
        hist = np.asarray(hist, np.int32).reshape(-1)
        bucket = bucket_for(int(hist.size), self._spec_buckets)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, : hist.size] = hist
        self.state = self._spec_prefill_exe(bucket)(
            self.draft_params, self.state, self._put(padded, jnp.int32),
            self._put(slot, jnp.int32), self._put(hist.size, jnp.int32))

    def _prefill_exe(self, bucket: int, first: bool = True):
        key = ("slot_prefill", bucket, first) + self._sampler_key()

        def build():
            e = self.econ
            rep = self._rep
            i32 = lambda shape=(): jax.ShapeDtypeStruct(shape, jnp.int32)
            f32 = lambda: jax.ShapeDtypeStruct((), jnp.float32)
            if self.paged:
                fn = paged_prefill_program(
                    self.cfg, self.mesh, self.rules, eos_id=e.eos_id,
                    first=first,
                )
                jitted = jax.jit(
                    fn,
                    in_shardings=(self._p_sh, self._state_sh) + (rep,) * 8,
                    out_shardings=(self._state_sh, rep),
                    donate_argnums=(1,),
                )
                return jitted.lower(
                    self._params_sds, self._state_sds, i32((1, bucket)),
                    i32(), i32(), i32(), i32(), f32(), i32(), f32(),
                ).compile()
            fn = slot_prefill_program(
                self.cfg, self.mesh, self.rules, eos_id=e.eos_id,
            )
            jitted = jax.jit(
                fn,
                in_shardings=(self._p_sh, self._state_sh) + (rep,) * 7,
                out_shardings=(self._state_sh, rep),
                donate_argnums=(1,),
            )
            return jitted.lower(
                self._params_sds, self._state_sds, i32((1, bucket)),
                i32(), i32(), i32(), f32(), i32(), f32(),
            ).compile()

        return self.aot.get(key, build)

    def _copy_exe(self):
        """Block-copy executable for the prefix cache's COW tail."""
        key = ("paged_copy",) + self._sampler_key()

        def build():
            fn = paged_copy_program(self.cfg, self.mesh, self.rules)
            i32 = jax.ShapeDtypeStruct((), jnp.int32)
            jitted = jax.jit(
                fn,
                in_shardings=(self._state_sh, self._rep, self._rep),
                out_shardings=self._state_sh,
                donate_argnums=(0,),
            )
            return jitted.lower(self._state_sds, i32, i32).compile()

        return self.aot.get(key, build)

    # -- host-tier transport (fixed-shape, AOT like everything else) ----
    def _block_payload_sds(self) -> dict:
        out = {}
        for name, c in self._state_sds["cache"].items():
            ax = len(c.shape) - 4
            out[name] = jax.ShapeDtypeStruct(
                c.shape[:ax] + c.shape[ax + 1:], c.dtype)
        return out

    def _lane_payload_sds(self) -> dict:
        out = {}
        for name, ax in self._lane_axes.items():
            c = self._state_sds["cache"][name]
            out[name] = jax.ShapeDtypeStruct(
                c.shape[:ax] + c.shape[ax + 1:], c.dtype)
        return out

    def _block_read_exe(self):
        """Read one KV block to replicated outputs (spill fetch)."""
        key = ("tier_block_read",) + self._sampler_key()

        def build():
            fn = paged_block_read_program(self.cfg, self.mesh, self.rules)
            i32 = jax.ShapeDtypeStruct((), jnp.int32)
            out_sh = {n: self._rep for n in self._state_sds["cache"]}
            # NOT donated: the read must leave the state intact
            jitted = jax.jit(fn, in_shardings=(self._state_sh, self._rep),
                             out_shardings=out_sh)
            return jitted.lower(self._state_sds, i32).compile()

        return self.aot.get(key, build)

    def _block_write_exe(self):
        """Write one KV block from host payloads (restore)."""
        key = ("tier_block_write",) + self._sampler_key()

        def build():
            fn = paged_block_write_program(self.cfg, self.mesh, self.rules)
            i32 = jax.ShapeDtypeStruct((), jnp.int32)
            pay = self._block_payload_sds()
            pay_sh = {n: self._rep for n in pay}
            jitted = jax.jit(fn, in_shardings=(self._state_sh, pay_sh,
                                               self._rep),
                             out_shardings=self._state_sh,
                             donate_argnums=(0,))
            return jitted.lower(self._state_sds, pay, i32).compile()

        return self.aot.get(key, build)

    def _lane_read_exe(self):
        """Read one lane's whole cache slice (slotted-layout spill)."""
        key = ("tier_lane_read",) + self._sampler_key()

        def build():
            fn = lane_read_program(self.cfg, self.mesh, self.rules,
                                   axes=self._lane_axes)
            i32 = jax.ShapeDtypeStruct((), jnp.int32)
            out_sh = {n: self._rep for n in self._lane_axes}
            jitted = jax.jit(fn, in_shardings=(self._state_sh, self._rep),
                             out_shardings=out_sh)
            return jitted.lower(self._state_sds, i32).compile()

        return self.aot.get(key, build)

    def _lane_write_exe(self):
        """Write one lane's whole cache slice (slotted-layout restore)."""
        key = ("tier_lane_write",) + self._sampler_key()

        def build():
            fn = lane_write_program(self.cfg, self.mesh, self.rules,
                                    axes=self._lane_axes)
            i32 = jax.ShapeDtypeStruct((), jnp.int32)
            pay = self._lane_payload_sds()
            pay_sh = {n: self._rep for n in pay}
            jitted = jax.jit(fn, in_shardings=(self._state_sh, pay_sh,
                                               self._rep),
                             out_shardings=self._state_sh,
                             donate_argnums=(0,))
            return jitted.lower(self._state_sds, pay, i32).compile()

        return self.aot.get(key, build)

    def prebuild(self) -> None:
        """Compile every executable this engine can ever dispatch.

        Prefix hits and preemption resumes make the prefill schedule
        timing-dependent (a prompt that hit the cache in warmup may miss
        in the timed pass and vice versa), so a warmup *trace* no longer
        guarantees coverage — the bench calls this instead to keep
        ``steady_builds_delta == 0`` an invariant rather than a race.
        """
        e = self.econ
        if not self.spec:       # spec engines never dispatch plain decode
            self._decode_exe()
        chunks = (e.prefill_chunk,) if (self.paged and e.prefill_chunk) \
            else self.buckets
        for C in chunks:
            self._prefill_exe(C, first=True)
            # continuation executables: chunked prefill always, and the
            # suffix prefill of any prefix-cache hit
            if self.paged and (e.prefill_chunk or e.prefix_cache):
                self._prefill_exe(C, first=False)
        if self.paged and e.prefix_cache:
            self._copy_exe()
        if self.tier is not None:
            # spill/restore transport rides the same AOT discipline: the
            # first eviction under load must not compile
            if self.paged:
                self._block_read_exe()
                self._block_write_exe()
            else:
                self._lane_read_exe()
                self._lane_write_exe()
        if self.spec:
            self._spec_exe()
            for C in self._spec_buckets:
                self._spec_prefill_exe(C)

    # ------------------------------------------------------------------
    # Request lifecycle
    # ------------------------------------------------------------------
    def validate(self, prompt, max_new_tokens: int) -> np.ndarray:
        """Admissibility checks for a request against this engine's
        config — pure config math, no engine state, so the router
        front-end can validate at its own admission boundary before any
        replica holds the request.  Returns the normalized prompt."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        bucket_for(prompt.size, self.buckets)  # raises if it can't fit
        if prompt.size + max_new_tokens - 1 > self.econ.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_len {self.econ.max_len}"
            )
        if self.paged:
            wc = blocks_for(prompt.size + max_new_tokens - 1,
                            self.econ.page_size)
            if wc > self.alloc.capacity:
                raise ValueError(
                    f"request needs up to {wc} KV blocks but the pool only "
                    f"has {self.alloc.capacity}"
                )
        return prompt

    def submit(self, prompt, *, max_new_tokens: int = 16,
               temperature: float = 0.0, top_k: int | None = None,
               top_p: float | None = None, rid: int | None = None,
               deadline_s: float | None = None) -> int:
        """Queue a request; returns its request id.  ``top_k``/``top_p``
        default to the engine-wide ``EngineConfig`` values.

        ``deadline_s`` is a per-request TTL measured from submission: a
        request still queued (or still decoding) when the deadline passes
        terminates with status ``"timeout"``, keeping whatever tokens it
        had emitted."""
        prompt = self.validate(prompt, max_new_tokens)
        eff_k = int(self.econ.top_k if top_k is None else top_k)
        eff_p = float(self.econ.top_p if top_p is None else top_p)
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError("deadline_s must be positive")
        if rid is None:
            rid = self._next_rid
        self._next_rid = max(self._next_rid, rid) + 1
        now = self.clock()
        deadline = None if deadline_s is None else now + float(deadline_s)
        if deadline is not None:
            self._has_deadlines = True
        self.queue.append(_Pending(
            rid, prompt, max_new_tokens, float(temperature), eff_k, eff_p,
            now, deadline=deadline))
        if self.obs.tracer is not None:
            self.obs.mark("submit", rid, track=self._track,
                          plen=int(prompt.size), max_new=max_new_tokens)
            self.obs.open_request(rid)
        return rid

    def cancel(self, rid: int) -> bool:
        """Cancel a request wherever it is.

        Queued: removed from the queue.  Mid-decode (or mid-prefill): the
        lane is evicted — block refs drop, the deficit commitment refunds
        — exactly like a finish.  Either way the request completes with
        status ``"cancelled"`` and whatever tokens it had emitted.
        Returns False (no-op) if the request already completed; raises
        ``KeyError`` for an unknown rid."""
        if rid in self.completions:
            return False
        for idx, req in enumerate(self.queue):
            if req.rid == rid:
                del self.queue[idx]
                self._terminate_queued(req, "cancelled")
                return True
        if rid in self.parked:
            self._terminate_queued(self.parked.pop(rid), "cancelled")
            return True
        for slot, s in enumerate(self.slots):
            if s is not None and s.rid == rid:
                self._terminate(slot, "cancelled")
                return True
        raise KeyError(f"unknown rid {rid}")

    def _find_lane(self, rid: int) -> int | None:
        for i, s in enumerate(self.slots):
            if s is not None and s.rid == rid:
                return i
        return None

    def hold(self, rid: int) -> bool:
        """Pause a decoding lane in place (e.g. an agent turn waiting on a
        tool result): the lane keeps its slot and — for KV layouts — its
        device state, but its ``active`` bit clears so decode steps skip
        it.  Held lanes are first in line for preemption and, after
        ``park_idle_s``, are swept off HBM entirely into the host tier
        (:meth:`_park`).  Recurrent/hybrid lanes spill to the host tier
        *immediately* — the decode program zeroes inactive lanes'
        recurrent leaves, so the device copy is dead the moment the hold
        lands — which is why holding them requires a host tier.  Returns
        False if the tier refuses the snapshot (lane keeps decoding);
        idempotent for an already-held lane.  Raises ``KeyError`` for a
        rid that is not on a lane (queued/parked/completed requests can't
        be held)."""
        slot = self._find_lane(rid)
        if slot is None:
            raise KeyError(f"rid {rid} is not on a lane")
        s = self.slots[slot]
        if s.held:
            return True
        if s.prefilled < s.plen or s.generated < 1:
            raise ValueError(f"rid {rid} is mid-prefill; cannot hold")
        if self.rec:
            if not self._lane_spillable(s):
                raise ValueError(
                    "holding a recurrent/hybrid lane requires a host tier "
                    "(the freeze zeroes inactive lanes' recurrent state)")
            if not self._spill_lane(slot):
                return False
        s.held = True
        s.held_since = self.clock()
        self._active_mirror[slot] = False
        self._active_dirty = True
        self.counters["holds"] += 1
        if self.obs.tracer is not None:
            self.obs.mark("hold", rid, track=self._track, slot=slot)
        return True

    def release(self, rid: int) -> None:
        """Resume a held or parked request.  A held lane flips its
        ``active`` bit back on (recurrent lanes restore their hold-time
        snapshot from the host tier first — the device copy was zeroed);
        a parked request re-enters the queue at the front and resumes
        through the normal admission path, O(copy) if its spill survived.
        Raises ``KeyError`` if the rid is neither held nor parked."""
        if rid in self.parked:
            self.queue.appendleft(self.parked.pop(rid))
            self.counters["releases"] += 1
            if self.obs.tracer is not None:
                self.obs.mark("release", rid, track=self._track,
                              parked=True)
            return
        slot = self._find_lane(rid)
        if slot is None or not self.slots[slot].held:
            raise KeyError(f"rid {rid} is not held or parked")
        s = self.slots[slot]
        if self.rec:
            sp = self.tier.pop_lane(rid)
            assert sp is not None and sp.kind == "lane", \
                "held recurrent lane lost its hold-time spill"
            self.state = self._lane_write_exe()(
                self.state,
                {k: self._put(v, v.dtype) for k, v in sp.leaves.items()},
                self._put(slot, jnp.int32))
            self.counters["restores"] += 1
            self.counters["restored_bytes"] += sp.nbytes
            s.held = False
            s.held_since = None
            self._active_mirror[slot] = True
            # push the whole mirror NOW: any decode before the push would
            # freeze-zero the just-written recurrent leaves, and the
            # fused sampler zeroed the held lane's ``tokens`` entry
            self._push_sched()
            self._sched_dirty = False
        else:
            s.held = False
            s.held_since = None
            self._active_mirror[slot] = True
            # the fused sampler writes 0 into inactive lanes' ``tokens``
            # leaf, so the lane's decode input token must be re-pushed
            # from the host mirror along with the active bit
            self._sched_dirty = True
        if self.spec:
            # a held lane's RECURRENT draft leaves were freeze-zeroed
            # while inactive (a KV draft survives via lazy overwrite,
            # but rebuilding unconditionally keeps one code path);
            # committed history fully determines the draft state
            comp = self.live[rid]
            self._spec_draft_prefill(slot, np.concatenate([
                s.prompt,
                np.asarray(comp.tokens[: s.generated - 1], np.int32)]))
        self.counters["releases"] += 1
        if self.obs.tracer is not None:
            self.obs.mark("release", rid, track=self._track, slot=slot)

    def _park(self, slot: int) -> None:
        """Sweep a long-held lane off HBM: spill (unless its hold-time
        snapshot already covers it), then preempt — the held routing in
        :meth:`_preempt` sends the pending to ``self.parked`` rather than
        the queue.  If the tier refuses the spill the lane parks anyway;
        :meth:`release` then resumes it via bitwise replay."""
        s = self.slots[slot]
        if not self.tier.has_lane(s.rid) and self._lane_spillable(s):
            self._spill_lane(slot)
        self._preempt(slot, spill=False)

    def free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def has_work(self) -> bool:
        # parked requests count: they are incomplete work, just off-HBM
        # (drain() callers must release() them or they never finish)
        return bool(self.queue) or bool(self.parked) \
            or any(s is not None for s in self.slots)

    def _put(self, x, dtype):
        # np.array copies: on CPU a suitably aligned host buffer is aliased
        # zero-copy, and the host mirrors (tables, tokens, active, ...) are
        # mutated in place while an executable reading them may still be
        # queued behind async dispatch
        return jax.device_put(np.array(x, dtype=dtype), self._rep)

    # -- paged block bookkeeping ---------------------------------------
    def _can_admit(self, req: _Pending) -> bool:
        if not self.paged:
            return True
        bs = self.econ.page_size
        if self.econ.admission == "preempt":
            # immediate need only: blocks for the first prefill chunk (a
            # prefix hit can only shrink it).  Growth past that preempts.
            C = self.econ.prefill_chunk or bucket_for(
                req.prompt.size, self.buckets)
            need = max(blocks_for(min(C, int(req.prompt.size)), bs),
                       req.min_free)
            return self.alloc.available >= need
        limit = req.limit if req.resume else \
            req.prompt.size + req.max_new_tokens - 1
        wc = blocks_for(limit, bs)
        # conservative: only admit when the pool can still cover every
        # live lane's worst case plus this one — decode growth can then
        # never find the pool empty (cached blocks count: alloc reclaims
        # them, and a prefix hit that revives one also releases a unit of
        # commitment)
        return self.alloc.available - self._deficit >= wc

    def _pick_victim(self) -> int | None:
        """Lowest-priority occupied lane: held (idle) lanes first — they
        aren't decoding, so evicting one costs nothing now — then the
        highest rid (last arrived)."""
        best = None
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            if best is None or (s.held, s.rid) > (
                    self.slots[best].held, self.slots[best].rid):
                best = i
        return best

    def _alloc_block(self, slot: int) -> int | None:
        """One block for ``slot``; under ``admission='preempt'`` an empty
        pool evicts the lowest-priority lane (possibly ``slot`` itself —
        then returns None and the caller abandons the lane's step)."""
        if self.faults is not None and self.faults.fire("alloc"):
            # injected transient pool exhaustion: the requesting lane
            # retries through the same preempt-and-requeue path a real
            # fault would use, so invariants (refs, deficit) hold
            self.counters["faults_injected"] += 1
            if self.obs.tracer is not None:
                self.obs.instant("fault", track=self._track, site="alloc",
                                 rid=self.slots[slot].rid)
            self._retry_lane(slot, "injected block-alloc fault")
            return None
        while True:
            try:
                return self.alloc.alloc()
            except RuntimeError:
                if self.econ.admission != "preempt":
                    raise
                victim = self._pick_victim()
                if victim is None:
                    raise
                self._preempt(victim)
                if victim == slot:
                    return None

    def _map_blocks(self, slot: int, need: int) -> bool:
        """Grow ``slot``'s table to ``need`` blocks.  False iff the lane
        itself was preempted to find room (it no longer exists)."""
        while self.tables.mapped(slot) < need:
            b = self._alloc_block(slot)
            if b is None:
                return False
            self.tables.append(slot, b)
            if self.econ.admission == "deficit":
                self._deficit -= 1
            self._tables_dirty = True
        return True

    def preempt(self, slot: int) -> None:
        """Host-initiated preempt-and-requeue of the live lane ``slot``
        (any layout / state kind) — the hook an external priority
        scheduler uses to reclaim a lane for more urgent work.

        Same policy as the paged engine's pool-pressure preemption: the
        request requeues at the queue FRONT with its prompt, emitted
        tokens, and sampling state; resume re-prefills ONLY the prompt
        (prefill-origin state is deterministic given the same bucket
        executable — bitwise for KV *and* recurrent kinds) and replays
        the emitted tokens through decode, so the resumed stream is
        bitwise the unpreempted one (asserted for the ssm family in
        tests)."""
        if self.slots[slot] is None:
            raise ValueError(f"slot {slot} is not serving a request")
        self._preempt(slot)

    def _lane_spillable(self, s: _Slot) -> bool:
        """Whether a lane's decode state can move to the host tier: it
        must be fully prefilled and mid-decode (mid-prefill lanes hold
        nothing replay can't rebuild cheaper), and the layout must have a
        transport (paged blocks, or declared lane leaf axes)."""
        if self.tier is None or s.generated < 1 or s.prefilled < s.plen:
            return False
        return self.paged or bool(self._lane_axes)

    def _capture_spill(self, slot: int) -> LaneSpill:
        """Copy a lane's decode state off-device into a LaneSpill (does
        not admit it to the tier — callers do, and fall back to replay
        when the tier refuses)."""
        s = self.slots[slot]
        if self.paged:
            exe = self._block_read_exe()
            payloads = []
            for b in self.tables.blocks(slot):
                out = exe(self.state, self._put(b, jnp.int32))
                payloads.append({k: np.asarray(v) for k, v in out.items()})
            return LaneSpill(s.rid, "paged", s.prefilled, s.generated,
                             blocks=payloads)
        out = self._lane_read_exe()(self.state, self._put(slot, jnp.int32))
        leaves = {k: np.asarray(v) for k, v in out.items()}
        return LaneSpill(s.rid, "lane", s.prefilled, s.generated,
                         leaves=leaves)

    def _spill_lane(self, slot: int) -> bool:
        """Capture + admit a lane spill; counters either way."""
        sp = self._capture_spill(slot)
        if self.tier.put_lane(sp):
            self.counters["spills"] += 1
            self.counters["spilled_bytes"] += sp.nbytes
            if self.obs.tracer is not None:
                self.obs.mark("spill", sp.rid, track=self._track, slot=slot,
                              kind=sp.kind, nbytes=sp.nbytes)
            return True
        self.counters["spill_drops"] += 1
        return False

    def _spill_block(self, block: int, key: bytes | None) -> None:
        """``BlockAllocator.on_evict`` hook: an LRU-reclaimed prefix
        block's KV moves to the host tier before its device block is
        reused — the cached chain spills instead of dying, and a later
        admission (or the router's scoring) finds it via
        ``HostTier.match_chain``.  The read's host fetch blocks until the
        copy lands, so the block's new owner can't race it."""
        if key is None:
            return
        out = self._block_read_exe()(self.state, self._put(block, jnp.int32))
        payload = {k: np.asarray(v) for k, v in out.items()}
        nb = sum(a.nbytes for a in payload.values())
        if self.tier.put_block(key, payload):
            self.counters["prefix_spills"] += 1
            self.counters["spilled_bytes"] += nb
            if self.obs.tracer is not None:
                self.obs.instant("prefix_spill", track=self._track,
                                 nbytes=nb)
        else:
            self.counters["spill_drops"] += 1

    def _preempt(self, slot: int, *, spill: bool = True) -> None:
        """Evict a live lane back to the host queue: its emitted tokens
        and sampling state requeue as a resume request, the table row
        nulls (paged), and every block reference drops.  With a host
        tier the lane's decode state spills first, so the resume is an
        O(copy) restore; otherwise (or when the tier refuses, or
        ``spill=False`` — fault retries recompute rather than restore
        possibly-poisoned state) the resume replays the stream bitwise
        (see :class:`_Pending`).  A held lane's pending goes to
        ``self.parked`` instead of the queue — preempting idle work IS
        parking it early."""
        s = self.slots[slot]
        comp = self.live[s.rid]
        if spill and self._lane_spillable(s) \
                and not (s.held and self.tier.has_lane(s.rid)):
            # held recurrent lanes spilled at hold() time (the device
            # copy has been zeroed by the freeze since) — their existing
            # spill is the truth; everything else captures fresh now
            self._spill_lane(slot)
        if self.paged:
            # min_free damps re-admission until the pool can cover one
            # block MORE than the lane held — instantly re-admitting the
            # victim into the slot it just vacated would recompute the
            # same prefill chunks every step until the evictor actually
            # frees something.  Capped at the lane's worst case: mapped+1
            # on a fully-grown victim would otherwise exceed what an
            # empty pool can offer.
            wc = blocks_for(s.limit, self.econ.page_size)
            min_free = min(self.tables.mapped(slot) + 1, wc)
        else:
            min_free = 0        # slotted lanes hold no pool resources
        pending = _Pending(
            s.rid, s.prompt, comp.max_new_tokens, s.temperature, s.top_k,
            s.top_p, comp.submit_time, deadline=s.deadline, resume=True,
            limit=s.limit, replay=tuple(comp.tokens),
            min_free=0 if s.held else min_free)
        if s.held:
            # parked: off the queue until release() — min_free resets
            # because the pool pressure it damped will be long gone
            self.parked[s.rid] = pending
            self.counters["parked"] += 1
            if self.obs.tracer is not None:
                self.obs.mark("park", s.rid, track=self._track, slot=slot)
        else:
            # resumes go to the FRONT: rid order (FCFS priority) is
            # preserved because successive victims within a step have
            # decreasing rids
            self.queue.appendleft(pending)
        self.slots[slot] = None
        self._active_mirror[slot] = False
        self._active_dirty = True
        if self.paged:
            if self.econ.admission == "deficit":
                # host-initiated preemption under deficit admission: give
                # back the lane's unallocated commitment (mapped blocks
                # free below; re-admission re-commits the worst case)
                self._deficit -= self._slot_wc[slot] - self.tables.mapped(slot)
            self._slot_wc[slot] = 0
            for b in self.tables.release(slot):
                self.alloc.free(b)
            self._tables_dirty = True
        self._last_op = "preempt"
        if not s.held:
            self.counters["preemptions"] += 1
            if self.obs.tracer is not None:
                self.obs.mark("preempt", s.rid, track=self._track, slot=slot,
                              emitted=len(comp.tokens))

    def _push_tables(self) -> None:
        """Re-push the host block-table mirror as the device state leaf.
        Must run before any executable that follows a table change — in
        particular before the decode after an eviction, so stale lanes'
        sink-routed writes can't land in re-allocated blocks."""
        if self._tables_dirty:
            self.state["tables"] = self._put(self.tables.table, jnp.int32)
            self._tables_dirty = False

    def _push_active(self) -> None:
        """Preemption clears a lane's ``active`` bit host-side (the device
        can't know) — re-push the mirror before the next decode so the
        evicted lane stops advancing."""
        if self._active_dirty:
            self.state["active"] = self._put(self._active_mirror, jnp.bool_)
            self._active_dirty = False

    def _push_sched(self) -> None:
        """Push the whole host scheduling mirror to the device — the
        lane-restore path seeds a mid-decode lane without running any
        executable.  Values for free/mid-prefill lanes are don't-cares
        (inactive lanes are masked; prefill re-seeds its own slot), so
        rebuilding every vector from ``self.slots`` is exact."""
        n = self.econ.max_slots
        lengths = np.zeros(n, np.int32)
        limits = np.zeros(n, np.int32)
        temps = np.zeros(n, np.float32)
        tks = np.zeros(n, np.int32)
        tps = np.zeros(n, np.float32)
        for i, s in enumerate(self.slots):
            if s is None:
                self._replay_mirror[i] = False
                continue
            lengths[i] = s.prefilled if s.generated == 0 \
                else s.plen + s.generated - 1
            limits[i] = s.limit
            temps[i] = s.temperature
            tks[i] = s.top_k
            tps[i] = s.top_p
            # the NEXT decode of this lane forces a recorded replay token
            self._replay_mirror[i] = s.generated < s.emit_from
        pushes = 1
        if self.faults is not None and self.faults.fire("sched_push"):
            # injected lost push: the host mirror (not device state) is
            # the scheduling truth, so recovery is re-running the same
            # push — exercised here by pushing twice, first one "lost"
            self.counters["faults_injected"] += 1
            self.obs.instant("fault", track=self._track, site="sched_push")
            pushes = 2
        for _ in range(pushes):
            self.state["tokens"] = self._put(self._tok_mirror, jnp.int32)
            self.state["lengths"] = self._put(lengths, jnp.int32)
            self.state["limits"] = self._put(limits, jnp.int32)
            self.state["temps"] = self._put(temps, jnp.float32)
            self.state["top_ks"] = self._put(tks, jnp.int32)
            self.state["top_ps"] = self._put(tps, jnp.float32)
            self.state["replay"] = self._put(self._replay_mirror, jnp.bool_)
            self.state["active"] = self._put(self._active_mirror, jnp.bool_)
        self._active_dirty = False

    def _promote_host_chain(self, keys: list[bytes], have: int) -> int:
        """Extend a device chain match by promoting host-tier prefix
        blocks back into the device index: allocate a free block, write
        the payload, publish it under its chain key, and park it cached
        — after which the ordinary lookup/share/COW machinery treats it
        like any cached chain.  Promotion only consumes the free list; it
        never evicts device-cached blocks to make room (the two tiers
        would thrash each other).  Returns the number promoted."""
        if self.tier is None:
            return 0
        n = 0
        wexe = None
        for key in keys[have:]:
            if not self.tier.has_block(key) or self.alloc.num_free == 0:
                break
            payload = self.tier.pop_block(key)
            b = self.alloc.alloc()      # free list non-empty: no eviction
            if wexe is None:
                wexe = self._block_write_exe()
            self.state = wexe(
                self.state,
                {k: self._put(v, v.dtype) for k, v in payload.items()},
                self._put(b, jnp.int32))
            self.alloc.publish(b, key)
            self.alloc.free(b)          # parks in the cached set, indexed
            nb = sum(a.nbytes for a in payload.values())
            self.counters["host_prefix_hits"] += 1
            self.counters["restored_bytes"] += nb
            n += 1
        if n and self.obs.tracer is not None:
            self.obs.instant("host_promote", track=self._track, blocks=n)
        return n

    def _chain_lookup(self, keys: list[bytes]) -> list[int]:
        """Device chain lookup, extended through the host tier: when the
        device match ends but the tier holds the next chain blocks,
        promote them and re-match — one admission-time lookup either
        way (the counter-free ``indexed`` probe sizes the device match
        first so hit/miss stats count once per admission)."""
        if self.tier is not None:
            have = 0
            for k in keys:
                if not self.alloc.indexed(k):
                    break
                have += 1
            if have < len(keys):
                self._promote_host_chain(keys, have)
        return self.alloc.lookup(keys)

    def _try_tier_restore(self, slot: int, req: _Pending) -> bool:
        """Resume fastest path: the host tier holds the lane's spilled
        decode state — copy it back and continue mid-decode.  No
        prefill, no replay for the covered tokens, O(bytes copied), and
        bitwise identical continuation (the payload IS the evicted
        state).  A stale spill (older than the replay record — e.g. the
        restore after it was refused for pool room) restores as a
        partial resume: the tokens past its coverage replay-force
        through decode exactly like ``_try_restore``'s partial match."""
        sp = self.tier.peek_lane(req.rid) if self.tier is not None else None
        if sp is None:
            return False
        s = self.slots[slot]
        plen = int(req.prompt.size)
        k_cov = sp.generated
        if sp.prefilled != plen or not (1 <= k_cov <= len(req.replay)) \
                or sp.kind != ("paged" if self.paged else "lane"):
            # a different prompt under a recycled rid, or a layout
            # mismatch: the spill is garbage for this resume
            self.tier.drop_lane(req.rid)
            return False
        if sp.kind == "paged":
            if self.alloc.available < len(sp.blocks):
                return False    # leave the spill; this resume replays
            wexe = self._block_write_exe()
            for payload in sp.blocks:
                b = self._alloc_block(slot)
                if b is None:
                    # an injected alloc fault preempted the lane itself;
                    # its partial table was released by the preempt and
                    # _admit's slot guard abandons the admission
                    return False
                self.state = wexe(
                    self.state,
                    {k: self._put(v, v.dtype) for k, v in payload.items()},
                    self._put(b, jnp.int32))
                self.tables.append(slot, b)
                if self.econ.admission == "deficit":
                    self._deficit -= 1
                self._tables_dirty = True
            # fresh private blocks: publication state restarts (the
            # chain keys may still be indexed by the original blocks, in
            # which case publish() dedups against them)
            s.pub_upto = 0
            s.hasher = None
            s.hashed = 0
        else:
            self.state = self._lane_write_exe()(
                self.state,
                {k: self._put(v, v.dtype) for k, v in sp.leaves.items()},
                self._put(slot, jnp.int32))
        self.tier.pop_lane(req.rid)
        seq = np.concatenate([req.prompt, np.asarray(req.replay, np.int32)])
        s.prefilled = plen
        s.generated = k_cov          # next decode input is seq[plen+k_cov-1]
        self._tok_mirror[slot] = int(seq[plen + k_cov - 1])
        self._active_mirror[slot] = True
        self.counters["restores"] += 1
        self.counters["restored_bytes"] += sp.nbytes
        if sp.kind == "lane" and self.rec:
            # recurrent leaves just restored: the device must see the
            # lane active BEFORE any later executable this step, or the
            # prefill freeze zeroes them again
            self._push_sched()
            self._sched_dirty = False
        else:
            self._sched_dirty = True
        if self.spec:
            # lane spills carry only TARGET state; rebuild the draft
            # cache from the committed history the spill covers (the
            # pending input seq[plen+k_cov-1] is the next decode input,
            # so the draft's written history stops just before it)
            self._spec_draft_prefill(slot, seq[: plen + k_cov - 1])
        if self.obs.tracer is not None:
            self.obs.mark("restore", req.rid, track=self._track, slot=slot,
                          source="host_tier", kind=sp.kind, nbytes=sp.nbytes)
        return True

    def _try_restore(self, slot: int, req: _Pending) -> bool:
        """Resume fast path: if the prefix cache still holds a block chain
        covering the whole prompt (typically the lane's own published
        blocks), share it and restore the lane MID-DECODE — no prefill, no
        replay, and bitwise-original KV for every covered position.  The
        device sees the restored lane through a scheduling-vector push.
        Chains truncated by LRU reclaim re-extend from the host tier
        (:meth:`_promote_host_chain`)."""
        k = len(req.replay)
        plen = int(req.prompt.size)
        bs = self.econ.page_size
        seq = np.concatenate([req.prompt, np.asarray(req.replay, np.int32)])
        written = seq[: plen + k - 1]        # positions whose KV existed
        chain = self._chain_lookup(prefix_keys(written, bs))
        matched = len(chain) * bs
        if matched < plen:
            # prefill + decode-replay path; _match_prefix counts this
            # admission's lookup so the hit rate stays per-admission
            return False
        for b in chain:
            self.tables.append(slot, self.alloc.share(b))
            if self.econ.admission == "deficit":
                self._deficit -= 1
            self._tables_dirty = True
        s = self.slots[slot]
        s.prefilled = plen
        s.generated = matched - plen + 1     # pending input at pos ``matched``
        s.pub_upto = len(chain)
        self.counters["prefix_lookup_tokens"] += int(written.size)
        self.counters["prefix_hit_tokens"] += matched
        self._tok_mirror[slot] = int(seq[matched])
        self._active_mirror[slot] = True
        self._sched_dirty = True             # pushed before the next decode
        if self.spec:
            # the shared chain restores only TARGET KV; rebuild the
            # draft cache over the restored history (everything before
            # the pending input seq[matched])
            self._spec_draft_prefill(slot, seq[:matched])
        return True

    # -- admission ------------------------------------------------------
    def _match_prefix(self, slot: int, prompt: np.ndarray) -> int:
        """Prefix-cache lookup for a fresh lane: share the longest cached
        block chain, COW the tail block when the match would cover the
        whole prompt (the sampling position is always recomputed), and
        map everything into the lane's table.  Returns the number of
        prompt positions the cache already holds (the prefill start)."""
        bs = self.econ.page_size
        plen = int(prompt.size)
        self.counters["prefix_lookup_tokens"] += plen
        chain = self._chain_lookup(prefix_keys(prompt, bs))
        if not chain:
            return 0
        # cap the match at plen - 1: the last prompt position is always
        # recomputed (its forward pass produces the first sampled token)
        cow = len(chain) * bs >= plen
        shared = chain[:-1] if cow else chain
        for b in shared:
            self.tables.append(slot, self.alloc.share(b))
            self._tables_dirty = True
            if self.econ.admission == "deficit":
                self._deficit -= 1
        if cow:
            src = chain[-1]
            dst = self._alloc_block(slot)
            if dst is None:       # preempt mode evicted the lane itself
                return -1
            self.state = self._copy_exe()(
                self.state, self._put(src, jnp.int32),
                self._put(dst, jnp.int32))
            self.tables.append(slot, dst)
            if self.econ.admission == "deficit":
                self._deficit -= 1
            self._tables_dirty = True
            self.counters["cow_copies"] += 1
        matched = len(shared) * bs + (bs if cow else 0)
        start = plen - 1 if cow else matched
        self.counters["prefix_hit_tokens"] += start
        self.slots[slot].pub_upto = len(chain)
        return start

    def _admit(self, req: _Pending, slot: int) -> None:
        plen = int(req.prompt.size)
        limit = req.limit if req.resume else plen + req.max_new_tokens - 1
        if self.obs.tracer is not None:
            self.obs.close_request(req.rid, admitted=True)
            self.obs.mark("admit", req.rid, track=self._track, slot=slot,
                          resume=req.resume)
        if not req.resume:
            self.live[req.rid] = Completion(
                rid=req.rid, prompt_len=plen,
                max_new_tokens=req.max_new_tokens,
                tokens=[], token_times=[], submit_time=req.submit_time,
                finish_time=0.0,
            )
            self.counters["admitted"] += 1
        else:
            self.counters["resumed"] += 1
        self.slots[slot] = _Slot(
            req.rid, plen, limit, req.temperature, req.top_k, req.top_p,
            req.prompt, 0, emit_from=len(req.replay), deadline=req.deadline,
        )
        if self.paged and self.econ.admission == "deficit":
            wc = blocks_for(limit, self.econ.page_size)
            self._slot_wc[slot] = wc
            self._deficit += wc
        # resume restore ladder: host-tier lane spill first (full O(copy)
        # coverage of everything the lane had written when evicted), then
        # the device/host prefix chains, then prefill + decode replay
        if req.resume and req.replay and self.tier is not None \
                and self.tier.has_lane(req.rid):
            if self._try_tier_restore(slot, req):
                return
            if self.slots[slot] is None:
                return      # the lane faulted/preempted itself mid-restore
        if self.paged:
            if self.econ.prefix_cache:
                if req.resume and req.replay and self._try_restore(slot, req):
                    # restored mid-decode: nothing to prefill
                    if self.obs.tracer is not None:
                        self.obs.mark("restore", req.rid, track=self._track,
                                      slot=slot)
                    return
                start = self._match_prefix(slot, req.prompt)
                if start < 0:
                    return            # the lane preempted itself mapping COW
                self.slots[slot].prefilled = start
        s = self.slots[slot]
        if self.paged and self.econ.prefill_chunk:
            s.chunk = self.econ.prefill_chunk
        else:
            # a prefix hit prefills only the suffix: bucket THAT length so
            # short suffixes of long prompts reuse the small executables
            s.chunk = bucket_for(plen - s.prefilled, self.buckets)
        self._prefill_next_chunk(slot)

    def _publish(self, slot: int) -> None:
        """Index every newly-full block of the lane under its chain key.
        A block is publishable once the lane's written KV covers it; keys
        digest the lane's *full* token sequence (prompt + generated), so
        decode-boundary blocks are shareable too — a later prompt that
        extends this request's output (or this request resuming after a
        preemption) rides the cached chain."""
        if not self.econ.prefix_cache:
            return
        s = self.slots[slot]
        bs = self.econ.page_size
        # positions with KV written: the prefilled prompt prefix, then one
        # per decode step (the newest sampled token is not yet written)
        kv_len = s.prefilled if s.generated == 0 else s.plen + s.generated - 1
        full = kv_len // bs
        if full <= s.pub_upto:
            return
        comp = self.live[s.rid]

        def block_tokens(j: int) -> bytes:
            # tokens of logical positions [j*bs, (j+1)*bs).  comp.tokens
            # is the rid's FULL emitted history (replay included), so
            # generated position p >= plen always holds tokens[p - plen]
            a, b = j * bs, (j + 1) * bs
            parts = []
            if a < s.plen:
                parts.append(s.prompt[a: min(b, s.plen)])
            if b > s.plen:
                parts.append(np.asarray(
                    comp.tokens[max(a - s.plen, 0): b - s.plen], np.int32))
            chunk = np.concatenate(parts) if len(parts) > 1 else parts[0]
            return np.ascontiguousarray(chunk, np.int32).tobytes()

        # incremental rolling hash (byte-identical to ``prefix_keys``):
        # each block costs O(bs), not a re-hash of the whole prefix
        if s.hasher is None:
            s.hasher = hashlib.sha256()
        blocks = self.tables.blocks(slot)
        for j in range(s.pub_upto, full):
            while s.hashed <= j:
                s.hasher.update(block_tokens(s.hashed))
                s.hashed += 1
            digest = s.hasher.digest()
            self.alloc.publish(blocks[j], digest)
            if self.tier is not None and self.alloc.indexed(digest):
                # the chain key is device-indexed again: drop any host
                # copy so every key has exactly one owner (check_tiered)
                self.tier.discard_block(digest)
        s.pub_upto = full

    def _prefill_next_chunk(self, slot: int) -> None:
        """Run one prefill chunk for the lane (the whole bucketed prompt
        when chunking is off; the unmatched suffix after a prefix hit).
        The chunk covering the prompt's last position samples the first
        token and activates the lane."""
        if self.faults is not None and self.faults.fire("prefill"):
            # injected dispatch failure BEFORE the executable runs: no
            # device state advanced, the lane just requeues and retries
            self.counters["faults_injected"] += 1
            if self.obs.tracer is not None:
                self.obs.instant("fault", track=self._track, site="prefill",
                                 rid=self.slots[slot].rid)
            self._retry_lane(slot, "injected prefill-dispatch fault")
            return
        s = self.slots[slot]
        if self.obs.tracer is None:
            self._prefill_chunk_run(slot)
        else:
            with self.obs.span("prefill_chunk", track=self._track,
                               rid=s.rid, start=s.prefilled, chunk=s.chunk):
                self._prefill_chunk_run(slot)

    def _prefill_chunk_run(self, slot: int) -> None:
        s = self.slots[slot]
        start = s.prefilled
        C = s.chunk
        end = min(start + C, s.plen)
        padded = np.zeros((1, C), np.int32)
        padded[0, : end - start] = s.prompt[start:end]
        if self.paged:
            if not self._map_blocks(slot, blocks_for(end, self.econ.page_size)):
                return                          # lane preempted itself
            self._push_tables()
            exe = self._prefill_exe(C, first=(start == 0))
            self.state, out = exe(
                self.params, self.state, self._put(padded, jnp.int32),
                self._put(slot, jnp.int32), self._put(start, jnp.int32),
                self._put(s.plen, jnp.int32), self._put(s.limit, jnp.int32),
                self._put(s.temperature, jnp.float32),
                self._put(s.top_k, jnp.int32), self._put(s.top_p, jnp.float32),
            )
        else:
            exe = self._prefill_exe(C)
            self.state, out = exe(
                self.params, self.state, self._put(padded, jnp.int32),
                self._put(slot, jnp.int32), self._put(s.plen, jnp.int32),
                self._put(s.limit, jnp.int32),
                self._put(s.temperature, jnp.float32),
                self._put(s.top_k, jnp.int32), self._put(s.top_p, jnp.float32),
            )
        s.prefilled = end
        self._last_op = "prefill"
        self.counters["prefill_chunks"] += 1
        self.counters["prefill_tokens"] += end - start
        self._publish(slot)
        if end < s.plen:
            return                              # more chunks to come
        self.counters["prefills"] += 1
        if self.spec:
            # the lane decodes from here: seed its draft cache with the
            # full prompt (draft state is never restored, always rebuilt)
            self._spec_draft_prefill(slot, s.prompt)

        tok = int(np.asarray(out)[0])
        if tok == NONFINITE_TOKEN:
            # the prompt's sampling position saw non-finite logits:
            # quarantine + bounded retry (or terminal "failed")
            self.counters["faults_detected"] += 1
            self._retry_lane(slot, "non-finite logits at prefill")
            return
        now = self.clock()
        comp = self.live[s.rid]
        s.generated = 1
        if s.emit_from >= 1:
            # replaying a preempted lane: force the RECORDED first token
            # as the next decode input.  Under greedy the regenerated
            # token equals it bitwise; under temperature>0 the regenerated
            # sample (drawn at a different key-stream position) must NOT
            # fork the conditioning away from the already-emitted history.
            # done stays False: the original run continued past here.
            self._tok_mirror[slot] = int(comp.tokens[0])
            self._active_mirror[slot] = True
            self._sched_dirty = True
            self.counters["replayed_tokens"] += 1
            if self.obs.tracer is not None:
                self.obs.mark("replay", s.rid, track=self._track,
                              pending=s.emit_from)
        else:
            comp.tokens.append(tok)
            comp.token_times.append(now)
            if self.obs.tracer is not None:
                self.obs.mark("first_token", s.rid, track=self._track)
            self._tok_mirror[slot] = tok
            done = (s.plen >= s.limit) or (
                self.econ.eos_id is not None and tok == self.econ.eos_id)
            self._active_mirror[slot] = not done
            if done:
                self._finish(slot, now)

    def _observe_terminal(self, comp: Completion) -> None:
        """Latency histograms + the request's terminal trace mark — the
        single exit point every termination path funnels through.  The
        histogram math mirrors ``launch/serve.py``'s summary exactly:
        TTFT = first token's host arrival - submit, per-token = total
        latency / emitted tokens (requests that emitted nothing record
        no latency, matching the historical printout)."""
        st = comp.status
        if comp.tokens:
            self.obs.metrics.histogram(f"ttft_ms_{st}").observe(
                max(0.0, (comp.token_times[0] - comp.submit_time) * 1e3))
            self.obs.metrics.histogram(f"tpot_ms_{st}").observe(
                max(0.0, (comp.finish_time - comp.submit_time) * 1e3
                    / len(comp.tokens)))
        if self.obs.tracer is not None:
            self.obs.close_request(comp.rid, admitted=False)
            self.obs.mark("terminal", comp.rid, track=self._track,
                          status=st, tokens=len(comp.tokens),
                          retries=comp.retries)

    def _finish(self, slot: int, now: float) -> None:
        # natural EOS/budget eviction: the device already deactivated the
        # lane itself, so no active-mirror push is owed
        self._terminate(slot, "ok", now=now, push_active=False)

    def _terminate(self, slot: int, status: str, *, error: str | None = None,
                   now: float | None = None,
                   push_active: bool = True) -> None:
        """Evict lane ``slot`` with a terminal ``status`` — the one
        eviction path for EOS/budget finishes ("ok"), deadline expiry
        ("timeout"), :meth:`cancel` ("cancelled"), and retry exhaustion
        ("failed").  Block refs drop and the deficit commitment refunds
        exactly as for a natural finish; host-initiated terminations
        (everything but "ok") also owe the device an active-bit push."""
        s = self.slots[slot]
        comp = self.live.pop(s.rid)
        comp.finish_time = self.clock() if now is None else now
        comp.status = status
        comp.error = error
        self.completions[s.rid] = comp
        self.slots[slot] = None
        self._active_mirror[slot] = False
        if push_active:
            self._active_dirty = True
        if self.paged:
            if self.econ.admission == "deficit":
                mapped = self.tables.mapped(slot)
                self._deficit -= self._slot_wc[slot] - mapped
                self._slot_wc[slot] = 0
            for b in self.tables.release(slot):
                self.alloc.free(b)
            self._tables_dirty = True
        if self.tier is not None:
            self.tier.drop_lane(s.rid)
        self.counters["evicted"] += 1
        self.counters[f"status_{status}"] += 1
        self._observe_terminal(comp)

    def _terminate_queued(self, req: _Pending, status: str,
                          error: str | None = None) -> None:
        """Terminal status for a request that is NOT on a lane (it holds
        no device resources).  A queued resume keeps the tokens its lane
        emitted before preemption."""
        if req.resume:
            comp = self.live.pop(req.rid)
        else:
            comp = Completion(
                rid=req.rid, prompt_len=int(req.prompt.size),
                max_new_tokens=req.max_new_tokens, tokens=[],
                token_times=[], submit_time=req.submit_time, finish_time=0.0,
            )
        comp.finish_time = self.clock()
        comp.status = status
        comp.error = error
        self.completions[req.rid] = comp
        if self.tier is not None:
            self.tier.drop_lane(req.rid)
        self.counters[f"status_{status}"] += 1
        self._observe_terminal(comp)

    def _retry_lane(self, slot: int, reason: str) -> None:
        """Quarantine + bounded retry for a faulted lane (non-finite
        logits, failed prefill dispatch, failed block alloc).  The request
        requeues through the preempt-and-requeue path — the resume
        replays its recorded tokens bitwise and reuses the existing
        bucketed executables, so retries keep ``steady_builds_delta == 0``
        — until its ``max_retries`` budget is spent; then it goes terminal
        with status "failed" (a structured result, not an exception)."""
        s = self.slots[slot]
        comp = self.live[s.rid]
        comp.retries += 1
        self.counters["retries"] += 1
        self._quarantine[slot] = 1
        if self.obs.tracer is not None:
            self.obs.mark("retry", s.rid, track=self._track, reason=reason,
                          retries=comp.retries)
        if comp.retries > self.econ.max_retries:
            self._terminate(slot, "failed", error=reason)
        else:
            # spill=False, and any earlier spill drops: a faulted lane's
            # state is suspect — the retry recomputes via prefill+replay
            # instead of restoring a possibly-poisoned copy O(fast)
            if self.tier is not None:
                self.tier.drop_lane(s.rid)
            self._preempt(slot, spill=False)

    def _expire_deadlines(self) -> None:
        """Terminate every queued or live request whose deadline passed.
        Queued requests simply leave the queue; live lanes evict with the
        full resource refund."""
        now = self.clock()
        expired = [r for r in self.queue
                   if r.deadline is not None and now >= r.deadline]
        if expired:
            dead = {r.rid for r in expired}
            self.queue = deque(r for r in self.queue if r.rid not in dead)
            for req in expired:
                self._terminate_queued(req, "timeout")
        for rid in [r for r, req in self.parked.items()
                    if req.deadline is not None and now >= req.deadline]:
            self._terminate_queued(self.parked.pop(rid), "timeout")
        for slot, s in enumerate(self.slots):
            if s is not None and s.deadline is not None \
                    and now >= s.deadline:
                self._terminate(slot, "timeout", now=now)

    def _note_kv_usage(self, decoding: frozenset = frozenset()) -> None:
        """Update the cache-usage high-water mark.  Paged reads the
        allocator's monotone peak (same-step evictions can't hide it);
        slotted KV is sampled right after the decode write (``decoding`` =
        lanes whose new token's KV is on device but not yet in the
        ``generated`` mirror) so eviction-step usage isn't under-counted.
        Recurrent/hybrid lanes cost a fixed per-lane share — their state
        is O(1) in sequence length — so usage is occupancy-proportional
        (the hybrid KV segment is folded into that per-lane constant)."""
        if self.paged:
            used = _exact_share(self.kv_reserved_bytes,
                                self.alloc.peak_in_use, self._num_blocks)
        elif self.kind == "kv":
            ntok = sum(
                s.prefilled + max(0, s.generated - 1) + (i in decoding)
                for i, s in enumerate(self.slots) if s is not None
            )
            used = _exact_share(self.kv_reserved_bytes, ntok,
                                self.econ.max_slots * self.econ.max_len)
        else:
            used = _exact_share(self.kv_reserved_bytes,
                                sum(s is not None for s in self.slots),
                                self.econ.max_slots)
        self._kv_gauge.set_max(used)

    def _advance_lane(self, i: int, tok: int, now: float) -> str:
        """Commit ONE fetched token for lane ``i`` — the per-token host
        walk shared by the plain decode step (one call per lane) and the
        speculative verify row (one call per accepted position, in row
        order).  Returns the outcome: ``"fault"`` (non-finite sentinel:
        lane quarantined + requeued, nothing committed), ``"replay"``
        (preemption replay: recorded token force-fed, nothing emitted),
        ``"done"`` (emitted and finished), or ``"ok"`` (emitted)."""
        s = self.slots[i]
        if tok == NONFINITE_TOKEN:
            # lane reported non-finite logits: its sample is invalid and
            # nothing is emitted — quarantine + bounded retry via
            # preempt-and-requeue (the resume replays the recorded
            # tokens bitwise), or terminal "failed" once the retry
            # budget is spent
            self.counters["faults_detected"] += 1
            self._retry_lane(i, "non-finite logits at decode")
            return "fault"
        s.generated += 1
        comp = self.live[s.rid]
        replaying = s.generated <= s.emit_from
        if replaying:
            # preemption replay: force the RECORDED token as the next
            # input (== the regenerated one under greedy; a stochastic
            # resample at a different key-stream position must not fork
            # the conditioning away from the emitted history).  No
            # re-emission, no done: the original run continued past
            # every replayed position.
            self._tok_mirror[i] = int(comp.tokens[s.generated - 1])
            self._sched_dirty = True
            self.counters["replayed_tokens"] += 1
        else:
            comp.tokens.append(tok)
            comp.token_times.append(now)
            self._tok_mirror[i] = tok
        if self.paged and \
                (s.plen + s.generated - 1) % self.econ.page_size == 0:
            self._publish(i)
        if replaying:
            return "replay"
        done = (s.plen + s.generated - 1 >= s.limit) or (
            self.econ.eos_id is not None and tok == self.econ.eos_id)
        if done:
            self._finish(i, now)
            return "done"
        return "ok"

    # ------------------------------------------------------------------
    # The serving loop
    # ------------------------------------------------------------------
    def step(self) -> bool:
        """Advance in-flight chunked prefills (one chunk per lane), admit
        every queued request a free slot (and, paged, the block budget)
        can take, then advance all fully-prefilled lanes by one token.
        Returns False when idle.

        Traced, the step is the span ``engine.step`` (numbered by the
        decode steps before it), holding ``engine.admit`` (the admission
        loop), ``decode`` (``decode.dispatch`` then ``decode.fetch``, the
        one host sync) and ``engine.advance`` (the per-lane bookkeeping
        after the fetch, to the end of the step)."""
        if self.obs.tracer is None:
            return self._step()
        with self.obs.span("engine.step", track=self._track,
                           step_num=self.counters["decode_steps"]):
            return self._step()

    def _step(self) -> bool:
        progressed = False
        advance_sid = None
        if self._has_deadlines:
            self._expire_deadlines()
        if self.tier is not None and self.econ.park_idle_s is not None:
            now = self.clock()
            for slot, s in enumerate(self.slots):
                if s is not None and s.held and s.held_since is not None \
                        and now - s.held_since >= self.econ.park_idle_s:
                    self._park(slot)
                    progressed = True
        for slot in range(self.econ.max_slots):
            s = self.slots[slot]
            if s is not None and s.prefilled < s.plen:
                self._prefill_next_chunk(slot)
                progressed = True

        admit_sid = None if self.obs.tracer is None else self.obs.begin(
            "engine.admit", track=self._track)
        for slot in self.free_slots():
            if self.slots[slot] is not None:    # refilled by a resume
                continue
            if self._quarantine[slot]:
                # fault cooldown: the lane sits out exactly one admission
                # round.  Consuming the countdown counts as progress — a
                # step where every free lane is quarantined must not read
                # as "idle" to drain()
                self._quarantine[slot] -= 1
                progressed = True
                continue
            if not self.queue or not self._can_admit(self.queue[0]):
                break
            self._admit(self.queue.popleft(), slot)
            progressed = True
        if admit_sid is not None:
            self.obs.end(admit_sid)

        def active():
            return [
                i for i, s in enumerate(self.slots)
                if s is not None and s.prefilled >= s.plen and not s.held
            ]

        active_slots = active()
        if active_slots and self.paged:
            # map the block each lane's next token lands in BEFORE the
            # step — the device never allocates.  Highest-priority lanes
            # map first, so a preemption pass evicts strictly later
            # arrivals (possibly a mapper itself, which then skips).
            for i in sorted(active_slots, key=lambda i: self.slots[i].rid):
                s = self.slots[i]
                if s is None:
                    continue                    # preempted by an earlier map
                next_pos = s.plen + s.generated - 1
                # spec: the verify row can write up to spec_k positions
                # past the next one — pre-map the whole horizon (capped
                # at the lane's budget) so rejected-step overshoot lands
                # in the lane's OWN blocks, never the write sink of an
                # unmapped entry and never a shared block (a freshly
                # mapped block is refcount-1 by construction)
                horizon = next_pos + (self.econ.spec_k if self.spec else 0)
                horizon = min(horizon, s.limit - 1)
                self._map_blocks(i, horizon // self.econ.page_size + 1)
            self._push_tables()
            active_slots = active()
        if active_slots:
            if self._sched_dirty:
                # lane restore or replay forcing rewrote the scheduling
                # mirror (tokens/active/lengths) — push it whole before
                # the device advances
                self._push_sched()
                self._sched_dirty = False
            else:
                self._push_active()
            # the decode span covers dispatch AND the token fetch — the
            # one per-step host sync — so its duration is the real
            # step-critical path, measured by the engine's own clock
            sid = dispatch_sid = None
            if self.obs.tracer is not None:
                sid = self.obs.begin("decode", track=self._track,
                                     lanes=len(active_slots))
                dispatch_sid = self.obs.begin("decode.dispatch",
                                              track=self._track)
            if self.spec:
                self.state, out = self._spec_exe()(
                    self.params, self.draft_params, self.state)
            else:
                exe = self._decode_exe()
                self.state, out = exe(self.params, self.state)
            self._last_op = "decode"
            self._note_kv_usage(frozenset(active_slots))
            self.counters["decode_steps"] += 1
            self.counters["dead_slot_steps"] += (
                self.econ.max_slots - len(active_slots))
            if dispatch_sid is not None:
                self.obs.end(dispatch_sid)
                fetch_sid = self.obs.begin("decode.fetch", track=self._track)
            # the one per-step host sync: (max_slots, k+1) verify rows
            # or sampled tokens
            fetched = np.asarray(out)
            if dispatch_sid is not None:
                self.obs.end(fetch_sid)
            if self.spec:
                rows = fetched
                self.counters["spec_steps"] += 1
                self.counters["spec_rounds"] += len(active_slots)
            else:
                toks = fetched
            if sid is not None:
                self.obs.end(sid)
                advance_sid = self.obs.begin("engine.advance",
                                             track=self._track)
            if self.faults is not None:
                lane = self.faults.pick("decode_logits", active_slots)
                if lane is not None:
                    # simulate the device having detected non-finite
                    # logits for this lane: flip its word in the fetched
                    # vector to the sentinel the real detector reports
                    self.counters["faults_injected"] += 1
                    if self.obs.tracer is not None:
                        self.obs.instant(
                            "fault", track=self._track, site="decode_logits",
                            rid=self.slots[lane].rid)
                    if self.spec:
                        rows = np.array(rows, copy=True)
                        rows[lane, 0] = NONFINITE_TOKEN
                    else:
                        toks = np.array(toks, copy=True)
                        toks[lane] = NONFINITE_TOKEN
            now = self.clock()
            if self.spec:
                for i in active_slots:
                    s = self.slots[i]
                    # accounting is per-SPECULATION: replay rounds force
                    # one recorded token and speculate nothing
                    replaying0 = s.generated < s.emit_from
                    c = 0
                    outcome = "ok"
                    for tok in rows[i]:
                        tok = int(tok)
                        if tok == UNCOMMITTED:
                            break       # first rejected/inactive position
                        outcome = self._advance_lane(i, tok, now)
                        if outcome == "fault":
                            break
                        c += 1
                        if outcome == "done":
                            break
                    self.counters["spec_committed"] += c
                    if not replaying0:
                        self.counters["spec_drafted"] += self.econ.spec_k
                        if c:
                            # the row's first commit scores the pending
                            # token (not a draft); commits 2..c each
                            # accept one draft proposal
                            self.counters["spec_accepted"] += c - 1
                        if outcome == "ok" and c <= self.econ.spec_k:
                            # the chain ended by draft mismatch (not by
                            # finishing, faulting, or running out of row)
                            self.counters["spec_rejected"] += 1
            else:
                for i in active_slots:
                    self._advance_lane(i, int(toks[i]), now)
            progressed = True
        self._note_kv_usage()
        if advance_sid is not None:
            self.obs.end(advance_sid)
        return progressed

    def drain(self) -> None:
        while self.step():
            pass

    def run(self, prompts: Sequence[Any], *, max_new_tokens: int = 16,
            temperature: float = 0.0, top_k: int | None = None,
            top_p: float | None = None) -> list[np.ndarray]:
        """Batch convenience: submit all, drain, return tokens in order."""
        rids = [
            self.submit(p, max_new_tokens=max_new_tokens,
                        temperature=temperature, top_k=top_k, top_p=top_p)
            for p in prompts
        ]
        self.drain()
        return [np.asarray(self.completions[r].tokens, np.int32) for r in rids]

    # ------------------------------------------------------------------
    # Crash-consistent snapshot / restore
    # ------------------------------------------------------------------
    # The engine's durable truth is entirely host-side: the queue, the
    # per-request Completions, and the recorded token streams.  Device
    # state (KV pool contents, block tables, the prefix index over pool
    # blocks) is a CACHE of that truth — a live lane's KV is recomputable
    # from its prompt + recorded tokens through the same preempt-resume
    # path the engine already uses under pool pressure.  A snapshot
    # therefore serializes every live lane as a front-of-queue resume
    # request and drops the allocator/prefix index (the pool it describes
    # died with the process); restore into a FRESH engine re-prefills and
    # replays, which is bitwise the uninterrupted stream under greedy
    # decoding (the PR-4 replay property).  Everything in the snapshot is
    # plain JSON, so it rides CheckpointManager's atomic meta.json.

    _SNAP_FORMAT = 1

    @staticmethod
    def _snap_pending(req: _Pending) -> dict:
        return {
            "rid": req.rid,
            "prompt": [int(t) for t in req.prompt],
            "max_new_tokens": req.max_new_tokens,
            "temperature": req.temperature,
            "top_k": req.top_k,
            "top_p": req.top_p,
            "submit_time": req.submit_time,
            "deadline": req.deadline,
            "resume": req.resume,
            "limit": req.limit,
            "replay": [int(t) for t in req.replay],
        }

    @staticmethod
    def _snap_completion(comp: Completion) -> dict:
        return {
            "rid": comp.rid,
            "prompt_len": comp.prompt_len,
            "max_new_tokens": comp.max_new_tokens,
            "tokens": [int(t) for t in comp.tokens],
            "token_times": [float(t) for t in comp.token_times],
            "submit_time": comp.submit_time,
            "finish_time": comp.finish_time,
            "status": comp.status,
            "error": comp.error,
            "retries": comp.retries,
        }

    @staticmethod
    def _load_completion(d: dict) -> Completion:
        return Completion(
            rid=int(d["rid"]), prompt_len=int(d["prompt_len"]),
            max_new_tokens=int(d["max_new_tokens"]),
            tokens=[int(t) for t in d["tokens"]],
            token_times=[float(t) for t in d["token_times"]],
            submit_time=float(d["submit_time"]),
            finish_time=float(d["finish_time"]),
            status=d["status"], error=d["error"], retries=int(d["retries"]),
        )

    def _econ_json(self) -> dict:
        # JSON round-trip normalization (tuples -> lists) so a snapshot
        # read back from disk compares equal to a live config
        return json.loads(json.dumps(dataclasses.asdict(self.econ)))

    def snapshot(self) -> dict:
        """Serialize the engine's host-side truth as a JSON-able dict.

        Live lanes become front-of-queue resume requests (rid order =
        FCFS priority), exactly as :meth:`preempt` would requeue them;
        the queued tail follows unchanged.  Device caches are dropped —
        see the section comment.  Consistent at any step boundary."""
        on_lane = sorted(
            (i for i, s in enumerate(self.slots) if s is not None),
            key=lambda i: self.slots[i].rid)
        pend = []
        for slot in on_lane:
            s = self.slots[slot]
            comp = self.live[s.rid]
            pend.append(self._snap_pending(_Pending(
                s.rid, s.prompt, comp.max_new_tokens, s.temperature,
                s.top_k, s.top_p, comp.submit_time, deadline=s.deadline,
                resume=True, limit=s.limit, replay=tuple(comp.tokens))))
        pend.extend(self._snap_pending(req) for req in self.queue)
        # parked requests resume DECODING after a restart: hold is
        # scheduling state, not durable truth — the restarted engine
        # requeues them like any preempted resume
        pend.extend(self._snap_pending(self.parked[r])
                    for r in sorted(self.parked))
        return {
            "format": self._SNAP_FORMAT,
            "arch": self.cfg.name,
            "engine": self._econ_json(),
            "queue": pend,
            # Completions of every in-flight rid (lane occupants and
            # queued resumes) — restore re-links them so replay forcing
            # and result continuity work across the restart
            "live": {str(r): self._snap_completion(c)
                     for r, c in self.live.items()},
            "completions": {str(r): self._snap_completion(c)
                            for r, c in self.completions.items()},
            "counters": dict(self.counters),
            "next_rid": self._next_rid,
        }

    def restore(self, snap: dict) -> None:
        """Rebuild serving state from :meth:`snapshot` into THIS engine,
        which must be freshly constructed (same arch + ``EngineConfig``)
        and never have served a request — the snapshot's device caches
        are gone, so restore re-derives them by re-prefilling prompts and
        replaying recorded tokens (bitwise the original stream under
        greedy decoding).  Drive with :meth:`step`/:meth:`drain` as
        usual afterwards."""
        if int(snap.get("format", -1)) != self._SNAP_FORMAT:
            raise ValueError(
                f"unsupported snapshot format {snap.get('format')!r}")
        if snap["arch"] != self.cfg.name:
            raise ValueError(
                f"snapshot is for arch {snap['arch']!r}, engine is "
                f"{self.cfg.name!r}")
        if snap["engine"] != self._econ_json():
            raise ValueError(
                "snapshot EngineConfig does not match this engine's")
        if self.has_work() or self.live or self.completions \
                or self.counters["admitted"]:
            raise ValueError("restore() requires a fresh engine")
        for req in snap["queue"]:
            deadline = req["deadline"]
            if deadline is not None:
                self._has_deadlines = True
            # min_free deliberately resets to 0: it damped re-admission
            # against the OLD engine's pool pressure, which died with it
            self.queue.append(_Pending(
                int(req["rid"]), np.asarray(req["prompt"], np.int32),
                int(req["max_new_tokens"]), float(req["temperature"]),
                int(req["top_k"]), float(req["top_p"]),
                float(req["submit_time"]), deadline=deadline,
                resume=bool(req["resume"]), limit=int(req["limit"]),
                replay=tuple(int(t) for t in req["replay"])))
        self.live = {int(r): self._load_completion(c)
                     for r, c in snap["live"].items()}
        self.completions = {int(r): self._load_completion(c)
                            for r, c in snap["completions"].items()}
        self.counters.update(snap["counters"])
        self._next_rid = int(snap["next_rid"])
        self.counters["snapshot_restores"] += 1
        self.obs.instant("snapshot_restore", track=self._track,
                         queued=len(self.queue), live=len(self.live))

    # -- per-request migration (router failover / drain) ---------------
    def export_request(self, rid: int) -> dict:
        """Remove one in-flight request from THIS engine and serialize it
        for migration to another replica (the router's drain path).

        A lane occupant is first preempted — migration IS a preemption,
        just resumed elsewhere: blocks free, the deficit refunds, and the
        emitted tokens ride along as the replay.  The returned dict is
        JSON-able, shaped like one entry of :meth:`snapshot`:
        ``{"pending": ..., "completion": ... | None}`` (the live
        Completion travels with a resume so replay forcing and result
        continuity survive the move).  Raises ``KeyError`` for unknown
        rids and ``ValueError`` for already-terminal ones."""
        if rid in self.completions:
            raise ValueError(f"rid {rid} is already terminal")
        for slot, s in enumerate(self.slots):
            if s is not None and s.rid == rid:
                self._preempt(slot)     # now front-of-queue, resume=True
                break
        if rid in self.parked:
            # pre-parked, or a held lane the preempt above just parked —
            # either way it migrates like any resume.  Its host-tier
            # spill stays behind: with a router-shared tier the importer
            # restores O(copy); otherwise the resume replays.
            req = self.parked.pop(rid)
            comp = self.live.pop(rid, None)
            self.counters["exported"] += 1
            if self.obs.tracer is not None:
                self.obs.mark("export", rid, track=self._track, resume=True)
            return {
                "pending": self._snap_pending(req),
                "completion":
                    None if comp is None else self._snap_completion(comp),
            }
        for idx, req in enumerate(self.queue):
            if req.rid == rid:
                del self.queue[idx]
                comp = self.live.pop(rid, None) if req.resume else None
                self.counters["exported"] += 1
                if self.obs.tracer is not None:
                    self.obs.mark("export", rid, track=self._track,
                                  resume=req.resume)
                return {
                    "pending": self._snap_pending(req),
                    "completion":
                        None if comp is None else self._snap_completion(comp),
                }
        raise KeyError(f"unknown rid {rid}")

    def import_request(self, snap: dict, *, front: bool = False) -> int:
        """Install a request exported from another replica (or rebuilt by
        the router from its own stream mirror after a crash) into this
        engine's queue.  ``front=True`` preserves the resume-first FCFS
        priority a preemption would have had.  The request then admits,
        re-prefills, and replays through the ordinary resume path —
        bitwise the uninterrupted stream under greedy decoding.

        Unlike :meth:`restore` this composes with a BUSY engine: rid
        uniqueness is checked against everything this engine knows."""
        req = snap["pending"]
        rid = int(req["rid"])
        if (rid in self.live or rid in self.completions
                or rid in self.parked
                or any(r.rid == rid for r in self.queue)):
            raise ValueError(f"rid {rid} already known to this engine")
        resume = bool(req["resume"])
        comp = snap.get("completion")
        if resume and comp is None:
            raise ValueError(f"resume import of rid {rid} without its "
                             "live Completion")
        prompt = self.validate(np.asarray(req["prompt"], np.int32),
                               int(req["max_new_tokens"]))
        deadline = req["deadline"]
        if deadline is not None:
            self._has_deadlines = True
        # min_free resets to 0: it damped re-admission against the OLD
        # replica's pool pressure, which stayed behind with it
        pending = _Pending(
            rid, prompt, int(req["max_new_tokens"]),
            float(req["temperature"]), int(req["top_k"]),
            float(req["top_p"]), float(req["submit_time"]),
            deadline=deadline, resume=resume, limit=int(req["limit"]),
            replay=tuple(int(t) for t in req["replay"]))
        if resume:
            self.live[rid] = self._load_completion(comp)
        (self.queue.appendleft if front else self.queue.append)(pending)
        self._next_rid = max(self._next_rid, rid + 1)
        self.counters["imported"] += 1
        if self.obs.tracer is not None:
            self.obs.mark("import", rid, track=self._track, resume=resume,
                          front=front)
        return rid

    def save_snapshot(self, mgr, step: int) -> None:
        """Persist :meth:`snapshot` through a
        :class:`~repro.checkpoint.manager.CheckpointManager` (atomic
        tmp-then-rename write; a crash mid-save leaves the previous
        checkpoint restorable)."""
        mgr.save(step, {}, extra_meta={"engine_snapshot": self.snapshot()})

    def restore_snapshot(self, mgr, step: int | None = None) -> int:
        """Restore from the checkpoint written by :meth:`save_snapshot`
        (latest when ``step`` is None).  Returns the checkpoint step."""
        step, meta = mgr.load_meta(step)
        if "engine_snapshot" not in meta:
            raise KeyError(f"checkpoint step {step} has no engine snapshot")
        self.restore(meta["engine_snapshot"])
        return step

    # ------------------------------------------------------------------
    # Invariants + stats
    # ------------------------------------------------------------------
    def check_invariants(self) -> None:
        """Conservation sweep — the fuzz harness runs this after every
        step.  Paged engines: free + live + cached partitions the pool,
        refcounts cover every mapping, every lane's written KV lies
        inside its mapped region (so no write can ever route to the null
        block while live), and deficit admission never over-commits.
        Recurrent/hybrid engines: every unoccupied lane's recurrent
        leaves are exactly zero (evict-time zeroing), checked when the
        last executable was a decode step — host-side evictions between
        executables (preemption, instant-finish prefills) zero one
        executable later.  Lifecycle: every completion carries a terminal
        status accounted in the status counters, and every in-flight
        Completion is owned by exactly one lane or one queued resume.

        A failed sweep dumps the flight recorder (when one is attached)
        before re-raising, so the event history leading up to the trip
        lands on disk with the assertion message."""
        try:
            self._check_invariants()
        except AssertionError as e:
            self.obs.record("invariant_failure", engine=self._track,
                            error=str(e))
            self.obs.dump("engine_invariant_failure", context={
                "engine": self._track,
                "error": str(e),
                "live_rids": sorted(self.live),
                "queued_rids": [r.rid for r in self.queue],
                "counters": dict(self.counters),
            })
            raise

    def _check_invariants(self) -> None:
        # metric-kind hygiene: the peak gauge must never have become a
        # counter (or vice versa) behind the MetricMap facade
        self.obs.metrics.check()
        kind = self.obs.metrics.kind
        assert kind("kv_peak_used_bytes") == "gauge", \
            "kv_peak_used_bytes must be a gauge (peak set, not a sum)"
        for k in ("decode_steps", "admitted", "evicted", "preemptions"):
            assert kind(k) == "counter", f"{k} must be a counter"
        for comp in self.completions.values():
            assert comp.status in STATUSES, (
                f"rid {comp.rid}: unknown status {comp.status!r}")
        assert sum(self.counters[f"status_{st}"] for st in STATUSES) \
            == len(self.completions), "status counters != completions"
        inflight = sorted(
            [s.rid for s in self.slots if s is not None]
            + [r.rid for r in self.queue if r.resume]
            + list(self.parked))
        assert inflight == sorted(self.live), (
            f"live rids {sorted(self.live)} != lane/resume/parked rids "
            f"{inflight}")
        for rid, req in self.parked.items():
            assert req.resume and req.rid == rid, (
                f"parked rid {rid} is not a resume pending")
        for slot, q in enumerate(self._quarantine):
            assert 0 <= q <= 1, f"slot {slot}: quarantine {q} out of range"
        if self.rec and self._last_op == "decode":
            free = [i for i, s in enumerate(self.slots) if s is None]
            assert self.rec.lanes_are_zero(self.state["cache"], free), (
                f"an evicted lane in {free} holds non-zero recurrent state")
        if self.spec and self._draft_rec and self._last_op == "decode":
            # the spec program's draft-side freeze is the only thing
            # zeroing dead draft lanes — sweep it like the target's
            free = [i for i, s in enumerate(self.slots) if s is None]
            assert self._draft_rec.lanes_are_zero(self.state["draft"], free), (
                f"an evicted lane in {free} holds non-zero DRAFT "
                "recurrent state")
        if not self.paged:
            if self.tier is not None:
                self.tier.check()
            return
        if self.tier is not None:
            check_tiered(self.alloc, self.tier)
        else:
            self.alloc.check()
        shared = self.econ.prefix_cache
        self.tables.check(refcount=self.alloc.refcount if shared else None)
        bs = self.econ.page_size
        for i, s in enumerate(self.slots):
            if s is None:
                assert self.tables.mapped(i) == 0, f"freed slot {i} maps blocks"
                continue
            kv_len = s.prefilled if s.generated == 0 \
                else s.plen + s.generated - 1
            assert kv_len <= self.tables.mapped(i) * bs, (
                f"slot {i}: {kv_len} KV positions written but only "
                f"{self.tables.mapped(i)} blocks mapped")
            for j, b in enumerate(self.tables.blocks(i)):
                assert self.alloc.refcount(b) >= 1, (
                    f"slot {i} maps non-live block {b}")
                if (j + 1) * bs > kv_len:
                    # no mapped block extending past the lane's committed
                    # KV may be shared: publication only ever indexes
                    # FULL blocks ((j+1)*bs <= kv_len at publish time),
                    # so any write past the commit point — a plain decode
                    # write, or spec verify overshoot on rejected steps —
                    # can only land in a block this lane owns outright
                    assert self.alloc.refcount(b) == 1, (
                        f"slot {i}: block {b} covers positions past "
                        f"kv_len {kv_len} but is shared "
                        f"(refcount {self.alloc.refcount(b)})")
        if self.econ.admission == "deficit":
            assert self.alloc.available >= self._deficit >= 0, (
                f"deficit {self._deficit} exceeds available "
                f"{self.alloc.available}")

    @property
    def stats(self) -> dict:
        """Engine + dispatch counters (mirrors ``SynkFunction.stats``)."""
        out = {
            **self.counters, **self.aot.stats,
            "executables": len(self.aot),
            "kv_layout": self.econ.kv_layout,
            "state_kind": self.kind,
            "kv_reserved_bytes": self.kv_reserved_bytes,
        }
        if self.spec:
            drafted = self.counters["spec_drafted"]
            out["spec_acceptance_rate"] = (
                self.counters["spec_accepted"] / drafted if drafted else 0.0)
            # mean committed chain length per lane per verify dispatch;
            # the sequential engine commits exactly 1.0 per lane-round,
            # so anything above 1.0 is speculation paying for itself
            rounds = self.counters["spec_rounds"]
            out["tokens_per_decode_dispatch"] = (
                self.counters["spec_committed"] / rounds if rounds else 0.0)
        if self.paged:
            out["prefix_cached_blocks"] = self.alloc.num_cached
            out["prefix_cache_evictions"] = self.alloc.cache_evictions
            looked = self.counters["prefix_lookup_tokens"]
            out["prefix_hit_rate"] = (
                self.counters["prefix_hit_tokens"] / looked if looked else 0.0)
        if self.tier is not None:
            out["host_tier"] = {
                "spilled_lanes": self.tier.spilled_lanes,
                "spilled_blocks": self.tier.spilled_blocks,
                "used_bytes": self.tier.used_bytes,
                "capacity_blocks": self.tier.capacity_blocks,
                "lane_spills": self.tier.lane_spills,
                "lane_restores": self.tier.lane_restores,
                "prefix_spills": self.tier.prefix_spills,
                "prefix_hits": self.tier.prefix_hits,
                "drops": self.tier.drops,
            }
        if self.faults is not None:
            out["faults"] = self.faults.stats()
        return out
