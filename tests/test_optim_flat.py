"""Flat-buffer layout and bucket partitioner (optim/flat.py + optim/buckets.py).

Multi-device behavior (bucketed == monolithic reduce, scatter round-trips,
train-step parity) runs in subprocesses — see test_core_multidevice.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim.buckets import (
    bucketed_all_reduce, flat_adam_apply, make_buckets,
    resolve_bucket_bytes,
)
from repro.optim.flat import (
    flat_adam_update, flatten, make_layout, unflatten,
)


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "emb": rng.normal(size=(37, 16)).astype(np.float32),
        "blocks": {
            "w": rng.normal(size=(3, 16, 16)).astype(np.float32),
            "b": jnp.asarray(rng.normal(size=(3, 16)), jnp.bfloat16),
        },
        "scalar": np.float32(2.5),
    }


def test_layout_roundtrip_mixed_dtypes_and_padding():
    tree = _tree()
    layout = make_layout(tree, align=512)
    assert layout.total % 512 == 0
    assert layout.total >= layout.unpadded
    buf = flatten(layout, tree)
    assert buf.shape == (layout.total,) and buf.dtype == jnp.float32
    back = unflatten(layout, buf)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=1e-2)
    # dtype override (flat fp32 optimizer state sharing the layout)
    back32 = unflatten(layout, buf, dtype=jnp.float32)
    for leaf in jax.tree.leaves(back32):
        assert leaf.dtype == jnp.float32


def test_layout_empty_tree():
    layout = make_layout({})
    assert layout.unpadded == 0
    buf = flatten(layout, {})
    assert buf.shape == (layout.total,)


def test_buckets_cover_exactly_at_param_boundaries():
    tree = _tree()
    layout = make_layout(tree, align=512)
    for bb in (64, 256, 1024, 4096, 1 << 30):
        buckets = make_buckets(layout, bucket_bytes=bb)
        # exact cover of [0, total)
        assert buckets.starts[0] == 0
        for i in range(1, buckets.num_buckets):
            assert buckets.starts[i] == buckets.starts[i - 1] + buckets.sizes[i - 1]
        assert buckets.total == layout.total
        # every interior boundary is a parameter boundary
        param_offsets = set(layout.offsets)
        for s in buckets.starts[1:]:
            assert s in param_offsets
    # giant target -> one bucket; tiny target -> one bucket per param
    assert make_buckets(layout, bucket_bytes=1 << 30).num_buckets == 1
    per_param = make_buckets(layout, bucket_bytes=1)
    assert per_param.num_buckets == len(layout.sizes)


def test_buckets_shard_padding():
    tree = _tree()
    layout = make_layout(tree, align=512)
    buckets = make_buckets(layout, bucket_bytes=1024, n_shards=8)
    for size, pad_to in zip(buckets.sizes, buckets.padded):
        assert pad_to % 8 == 0 and 0 <= pad_to - size < 8
    assert buckets.scattered_total == sum(buckets.padded)
    assert buckets.local_total * 8 == buckets.scattered_total


def test_buckets_validation():
    layout = make_layout(_tree())
    with pytest.raises(ValueError, match="bucket_bytes"):
        make_buckets(layout, bucket_bytes=0)
    with pytest.raises(ValueError, match="n_shards"):
        make_buckets(layout, n_shards=0)


def test_resolve_bucket_bytes_numeric_and_auto():
    from repro.roofline import V5E

    assert resolve_bucket_bytes(4.0) == 4 << 20
    assert resolve_bucket_bytes(0.05) == int(0.05 * (1 << 20))
    # auto: roofline-derived, positive, clamped to [1, 64] MiB
    b8 = resolve_bucket_bytes("auto", group_size=8, device_kind=V5E)
    assert (1 << 20) <= b8 <= (64 << 20)
    # bigger groups -> wire factor grows -> buckets no larger
    assert resolve_bucket_bytes(
        "auto", group_size=256, device_kind=V5E) <= b8
    # a chip missing from the peak table is an error, not a default
    with pytest.raises(ValueError, match="no peaks"):
        resolve_bucket_bytes("auto", group_size=8, device_kind="cpu")


def test_optconfig_bucket_mb_auto_builds_train_step(monkeypatch):
    """OptConfig(bucket_mb='auto') resolves through the train-step builder
    from the mesh's device kind; a kind without peaks raises."""
    from repro.configs import get_smoke_config
    from repro.optim import OptConfig
    from repro.roofline import V5E, analysis
    from repro.train.step import TrainSettings, build_train_step
    from repro.models.common import ShardRules

    mesh = jax.make_mesh((1, 1, 1), ("pod", "data", "model"))
    cfg = get_smoke_config("smollm-360m")
    opt = OptConfig(kind="adam", lr=1e-3, bucket_mb="auto")
    rules = ShardRules.for_mesh(mesh, faithful=True)
    with pytest.raises(ValueError, match="no peaks"):
        build_train_step(cfg, mesh, rules, opt, TrainSettings(faithful=True))
    kind = mesh.devices.flat[0].device_kind
    monkeypatch.setitem(analysis.PEAKS, kind, analysis.PEAKS[V5E])
    step = build_train_step(cfg, mesh, rules, opt, TrainSettings(faithful=True))
    assert step._flat_engine == "faithful"
    assert step._flat_buckets.bucket_bytes == resolve_bucket_bytes(
        "auto", group_size=1, device_kind=V5E)
    with pytest.raises(ValueError):
        OptConfig(bucket_mb="bogus")
    with pytest.raises(ValueError):
        OptConfig(bucket_mb=-1.0)


def test_bucketed_all_reduce_single_axis_identity():
    """On a 1-device axis the bucketed reduce is exact slicing+concat."""
    mesh = jax.make_mesh((1,), ("data",))
    from jax.sharding import PartitionSpec as P

    layout = make_layout(_tree())
    buckets = make_buckets(layout, bucket_bytes=512)
    buf = jnp.asarray(np.random.default_rng(2).normal(size=(layout.total,)),
                      jnp.float32)

    fn = jax.jit(jax.shard_map(
        lambda b: bucketed_all_reduce(b, buckets, "data"),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
    ))
    np.testing.assert_array_equal(np.asarray(fn(buf)), np.asarray(buf))


@pytest.mark.parametrize("n", [512, 1024 + 512])
def test_flat_adam_apply_kernel_matches_reference(n):
    rng = np.random.default_rng(n)
    p = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    m = jnp.asarray(np.abs(rng.normal(size=(n,))) * 0.1, jnp.float32)
    v = jnp.asarray(np.abs(rng.normal(size=(n,))) * 0.1, jnp.float32)
    step = jnp.int32(4)
    kw = dict(lr=1e-3, beta1=0.9, beta2=0.95, eps=1e-8, weight_decay=0.01)
    pk, mk, vk = flat_adam_apply(p, g, m, v, step, use_kernel=True, **kw)
    pr, mr, vr = flat_adam_apply(p, g, m, v, step, use_kernel=False, **kw)
    np.testing.assert_allclose(np.asarray(pk), np.asarray(pr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(mk), np.asarray(mr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(vk), np.asarray(vr), atol=1e-6)
    # reference path == the documented flat_adam_update math
    p2, m2, v2 = flat_adam_update(p, g, m, v, step, lr=1e-3)
    np.testing.assert_allclose(np.asarray(mr), np.asarray(m2), atol=0)
    np.testing.assert_allclose(np.asarray(vr), np.asarray(v2), atol=0)
