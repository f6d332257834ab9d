"""Compile rehearsal: the main-path Pallas kernels at real widths, compiled
for a described TPU v5e (no chip needed; the TPU compiler ships with
jaxlib).  Catches what interpret mode cannot: unaligned blocks, VMEM
overruns, ops Mosaic cannot lower.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, so under several test workers only
the worker running this file loads it.  Kernels the compiler still refuses
at these widths (paged attention, SSD) are recorded in ROADMAP.md.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flat_adam.kernel import flat_adam
from repro.kernels.rmsnorm.kernel import rmsnorm, rmsnorm_add
from repro.optim.buckets import make_buckets
from repro.train.step import flat_layout_for


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back without one
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)


def _compile(fn, *sds, donate=()):
    compiled = jax.jit(fn, donate_argnums=donate).lower(*sds).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("n_shards,length", [
    (1, 361_821_184),       # faithful: the whole flat buffer
    (4, 90_455_296),        # ZeRO on four chips: one shard, 4 MiB buckets
])
def test_flat_adam_compiles_at_smollm_flat_length(one_chip, n_shards, length):
    layout = flat_layout_for(get_config("smollm-360m"))
    buckets = make_buckets(layout, n_shards=n_shards)
    assert buckets.local_total == length
    vec = jax.ShapeDtypeStruct((length,), jnp.float32, sharding=one_chip)
    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = _compile(
        lambda p, g, m, v, t: flat_adam(p, g, m, v, t, lr=1e-3,
                                        interpret=False),
        vec, vec, vec, vec, step, donate=(0, 2, 3))
    # p', m', v' are written in place of the donated p, m, v (the
    # compiler counts padded allocations, so at least their bytes)
    assert compiled.memory_analysis().alias_size_in_bytes >= 3 * 4 * length
    # the kernel's name reaches its instruction, which a trace names it by
    assert re.search(r"%flat_adam[.\d]* = .* custom-call\(", compiled.as_text())


@pytest.mark.parametrize("fused_add", [False, True])
def test_rmsnorm_compiles_at_d960(one_chip, fused_add):
    x = jax.ShapeDtypeStruct((8 * 512, 960), jnp.bfloat16, sharding=one_chip)
    g = jax.ShapeDtypeStruct((960,), jnp.float32, sharding=one_chip)
    if fused_add:
        _compile(lambda x, r, g: rmsnorm_add(x, r, g, interpret=False),
                 x, x, g)
    else:
        _compile(lambda x, g: rmsnorm(x, g, interpret=False), x, g)


def test_flash_attention_compiles_at_smollm_heads(one_chip):
    cfg = get_config("smollm-360m")
    S, D = 2048, cfg.head_dim
    q = jax.ShapeDtypeStruct((1, cfg.n_heads, S, D), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, cfg.n_kv, S, D), jnp.bfloat16,
                              sharding=one_chip)
    _compile(lambda q, k, v: flash_attention_fwd(q, k, v, interpret=False),
             q, kv, kv)


def test_flash_attention_grad_compiles_at_smollm_train_shape(one_chip):
    """The train cell's attention core: forward and jax.grad through the
    kernels at smollm-360m heads, B=8, S=2048, bf16."""
    cfg = get_config("smollm-360m")
    B, S, D = 8, 2048, cfg.head_dim
    q = jax.ShapeDtypeStruct((B, S, cfg.n_heads, D), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, S, cfg.n_kv, D), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    fwd = _compile(lambda q, k, v: flash_attention(q, k, v, interpret=False),
                   q, kv, kv)
    assert re.search(r"%flash_attention_fwd[.\d]* = ", fwd.as_text())
    grad = _compile(jax.grad(loss, (0, 1, 2)), q, kv, kv).as_text()
    names = set(re.findall(r"%(flash_attention_\w+?)(?:\.\d+)? = ", grad))
    assert names == {"flash_attention_fwd", "flash_attention_bwd"}, names


def _serve_cell(one_chip, name):
    """A serve cell's configuration, lanes and abstract engine state on
    the described chip: (cfg, mesh, rules, params sds, params shardings,
    state sds, state shardings, max_len)."""
    import pathlib

    import numpy as np
    from jax.sharding import AxisType, Mesh

    from bench import harness
    from repro.models import registry
    from repro.models.common import ShardRules
    from repro.serve.cache import slot_state_specs
    from repro.train.step import shardings_for

    root = pathlib.Path(__file__).resolve().parents[1]
    cell = harness.load_cell(name, root=root)
    cfg = cell.adapter().arch(cell.config)
    lanes, max_len = int(cell.traffic["lanes"]), int(cell.traffic["max_len"])
    mesh = Mesh(np.array(list(one_chip.device_set)).reshape(1, 1, 1),
                ("pod", "data", "model"), axis_types=(AxisType.Auto,) * 3)
    rules = ShardRules.for_mesh(mesh)
    p_sh = shardings_for(mesh, registry.param_pspecs(cfg, rules))
    sds, sh = slot_state_specs(cfg, mesh, lanes, max_len)
    return (cfg, mesh, rules, registry.abstract_params(cfg), p_sh, sds, sh,
            max_len)


def _decode(cell):
    """The engine's decode executable over every lane of the cell."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.serve.step import slot_decode_program

    cfg, mesh, rules, p_sds, p_sh, sds, sh, _ = cell
    return jax.jit(slot_decode_program(cfg, mesh, rules),
                   in_shardings=(p_sh, sh),
                   out_shardings=(sh, NamedSharding(mesh, P())),
                   donate_argnums=(1,)).lower(p_sds, sds).compile()


def _kv_relayouts(text, leaf_shape):
    """Instructions that copy, transpose, concatenate or scatter a whole
    KV leaf or one layer of it, whatever the order of its dims."""
    kv = {tuple(sorted(d for d in shape if d > 1))
          for shape in (leaf_shape, leaf_shape[1:])}
    found = re.finditer(
        r"%\S+ = \w+\[([\d,]*)\](?:\{[^}]*\})? "
        r"(?:copy|transpose|concatenate|scatter)\(", text)
    return [m.group(0) for m in found
            if tuple(sorted(int(d) for d in m.group(1).split(",")
                            if d and int(d) > 1)) in kv]


def test_chat_decode_updates_the_kv_cache_in_place(one_chip):
    """The chat cell's decode step (64 lanes of 2048) for a described
    v5e: the position-minor cache is attended where it lies and the token
    written in place, so no whole leaf or layer of it is copied, re-laid
    out, restacked or scattered, and the step's temporaries stay small."""
    cell = _serve_cell(one_chip, "serve-smollm-360m-chat")
    cache = cell[5]["cache"]
    decode = _decode(cell)
    assert _kv_relayouts(decode.as_text(), cache["k"].shape) == []
    memory = decode.memory_analysis()
    assert memory.temp_size_in_bytes < 0.5e9
    # the donated cache leaves come back in place
    assert memory.alias_size_in_bytes >= sum(
        s.size * s.dtype.itemsize for s in cache.values())


def test_zamba2_serve_programs_fit_one_chip(one_chip):
    """The serve cell's decode step over every lane and its largest
    prefill bucket, at the cut's published widths with fp32 weights, for
    a described v5e: the compiler refuses a program that overflows the
    chip's memory, so a change that makes the cell overflow fails here.
    Both programs carry the model's named scopes, and decode neither
    copies nor restacks the hybrid layers' KV."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.serve.step import slot_prefill_program

    cell = _serve_cell(one_chip, "serve-zamba2-7b-longdoc")
    cfg, mesh, rules, p_sds, p_sh, sds, sh, max_len = cell
    rep = NamedSharding(mesh, P())
    i32 = lambda shape=(): jax.ShapeDtypeStruct(shape, jnp.int32)
    f32 = jax.ShapeDtypeStruct((), jnp.float32)

    decode = _decode(cell)
    prefill = jax.jit(slot_prefill_program(cfg, mesh, rules),
                      in_shardings=(p_sh, sh) + (rep,) * 7,
                      out_shardings=(sh, rep), donate_argnums=(1,)).lower(
        p_sds, sds, i32((1, max_len)), i32(), i32(), i32(), f32, i32(),
        f32).compile()
    weights = sum(int(np.prod(s.shape)) * 4 for s in jax.tree.leaves(p_sds))
    assert weights > 7e9                        # 1.76 B fp32 parameters
    for compiled in (decode, prefill):
        assert compiled.memory_analysis().argument_size_in_bytes > weights
        text = compiled.as_text()
        for scope in ("zamba.shared_block", "zamba.mamba", "zamba.ssd"):
            assert f"/{scope}/" in text, scope
    assert _kv_relayouts(decode.as_text(), sds["cache"]["k"].shape) == []
