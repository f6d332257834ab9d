from .cache import (
    RecurrentCache,
    bucket_for,
    make_slot_state,
    prompt_buckets,
    slot_state_specs,
)
from .engine import STATUSES, Completion, EngineConfig, ServeEngine
from .faults import (
    ENGINE_FAULT_SITES,
    FAULT_SITES,
    NONFINITE_TOKEN,
    REPLICA_FAULT_SITES,
    FaultPlan,
)
from .loop import ServeConfig, generate, generate_static
from .router import ReplicaHandle, Router, RouterConfig
from .paged import (
    BlockAllocator,
    HostTier,
    LaneSpill,
    SlotTables,
    blocks_for,
    check_tiered,
    make_paged_state,
    paged_state_specs,
    prefix_keys,
)
from .step import (
    jit_decode_step,
    jit_prefill,
    paged_copy_program,
    paged_decode_program,
    paged_prefill_program,
    sample_tokens,
    slot_decode_program,
    slot_prefill_program,
)

__all__ = [
    "Completion", "EngineConfig", "ServeEngine", "STATUSES",
    "FaultPlan", "FAULT_SITES", "ENGINE_FAULT_SITES",
    "REPLICA_FAULT_SITES", "NONFINITE_TOKEN",
    "Router", "RouterConfig", "ReplicaHandle",
    "ServeConfig", "generate", "generate_static",
    "RecurrentCache", "bucket_for", "make_slot_state",
    "prompt_buckets", "slot_state_specs",
    "BlockAllocator", "HostTier", "LaneSpill", "SlotTables", "blocks_for",
    "check_tiered", "make_paged_state", "paged_state_specs", "prefix_keys",
    "jit_decode_step", "jit_prefill", "sample_tokens",
    "slot_decode_program", "slot_prefill_program",
    "paged_copy_program", "paged_decode_program", "paged_prefill_program",
]
