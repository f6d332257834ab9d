import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
"""Multi-device semantics checks, run in a subprocess by the test suite
(the main pytest process must keep seeing 1 CPU device).

Usage: python -m repro.testing.md_checks <check_name | all>
Exits non-zero on failure.
"""
import sys

import numpy as np


def check_scatter_reduce():
    import jax.numpy as jnp
    import repro.core as synk

    ctx = synk.fork()
    assert ctx.n_data == 8, ctx.n_data

    def loss_fn(x, y, w):
        return jnp.mean((x @ w - y) ** 2)

    f = synk.function(loss_fn, [synk.Scatter(), synk.Scatter(), synk.Broadcast()],
                      synk.Reduce("mean"))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 8)).astype(np.float32)
    y = rng.normal(size=(64,)).astype(np.float32)
    w = rng.normal(size=(8,)).astype(np.float32)
    want = np.mean((x @ w - y) ** 2)
    np.testing.assert_allclose(f(x, y, w), want, rtol=1e-5)
    # paper §5.1 invariant: sliced == unsliced
    np.testing.assert_allclose(f(x, y, w, num_slices=4), want, rtol=1e-5)
    # sum/max/min/concat
    for op, ref in [("sum", np.sum), ("max", np.max), ("min", np.min)]:
        g = synk.function(lambda x: getattr(jnp, op)(x), [synk.Scatter()],
                          synk.Reduce("mean" if False else op))
        got = g(x)
        if op == "sum":
            np.testing.assert_allclose(got, ref(x), rtol=1e-5)
        else:
            np.testing.assert_allclose(got, ref(x), rtol=1e-6)
    c = synk.function(lambda x: x * 3.0, [synk.Scatter()], synk.Reduce("concat"))
    np.testing.assert_allclose(np.asarray(c(x)), x * 3, rtol=1e-6)
    pw = synk.function(lambda x: jnp.sum(x), [synk.Scatter()], synk.Reduce(None))
    assert np.asarray(pw(x)).shape == (8,)
    np.testing.assert_allclose(np.asarray(pw(x)).sum(), x.sum(), rtol=1e-5)


def check_indexing():
    import jax.numpy as jnp
    import repro.core as synk

    synk.fork()
    rng = np.random.default_rng(1)
    x = rng.normal(size=(64, 4)).astype(np.float32)

    f = synk.function(lambda x: jnp.mean(x), [synk.Scatter()], synk.Reduce("mean"))
    dx = synk.data(x)
    idx = rng.permutation(64)[:32]
    np.testing.assert_allclose(f(dx, batch=idx), x[idx].mean(), rtol=1e-5)

    # device-resident (paper §4.2 + §5.2): GLOBAL row ids.  Aligned case:
    # each worker's index chunk references its own shard (fast local take).
    ds = synk.scatter_data(x)
    aligned = np.concatenate(
        [i * 8 + rng.permutation(8)[:4] for i in range(8)])
    got = f(ds, batch=aligned)
    np.testing.assert_allclose(got, x[aligned].mean(), rtol=1e-5)


def check_indexing_global():
    """Regression: global ``batch=`` ids that cross shard boundaries must
    read the right rows (the old code applied them to local shards
    verbatim, silently reading wrong rows for anything past worker 0)."""
    import jax.numpy as jnp
    import repro.core as synk

    ctx = synk.fork()
    assert ctx.n_data == 8
    rng = np.random.default_rng(7)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    ds = synk.scatter_data(x)

    f = synk.function(lambda x: jnp.mean(x), [synk.Scatter()], synk.Reduce("mean"))
    # fully shuffled global indices: every chunk crosses shards
    idx = rng.permutation(64)[:32]
    np.testing.assert_allclose(f(ds, batch=idx), x[idx].mean(), rtol=1e-5)
    # repeated + reversed indices
    idx2 = np.asarray([63, 0, 0, 17, 40, 8, 55, 62] * 2)
    np.testing.assert_allclose(f(ds, batch=idx2), x[idx2].mean(), rtol=1e-5)

    # concat output: rows come back in request order, sliced to the
    # (pad-requiring) original length
    g = synk.function(lambda x: x * 1.0, [synk.Scatter()], synk.Reduce("concat"))
    idx3 = rng.permutation(64)[:12]            # 12 % 8 != 0 -> padded
    out = np.asarray(g(ds, batch=idx3))
    assert out.shape == (12, 4), out.shape
    np.testing.assert_allclose(out, x[idx3], rtol=1e-6)

    # pad > len(idx) edge case: 2 indices over 8 workers
    idx4 = np.asarray([5, 60])
    out = np.asarray(g(ds, batch=idx4))
    assert out.shape == (2, 4), out.shape
    np.testing.assert_allclose(out, x[idx4], rtol=1e-6)

    # gspmd backend: same global semantics
    h = synk.function(lambda x: jnp.mean(x), [synk.Scatter()],
                      synk.Reduce("mean"), backend="gspmd")
    np.testing.assert_allclose(h(ds, batch=idx), x[idx].mean(), rtol=1e-5)


def check_bucketed_reduce():
    """Bucketed flat all-reduce == monolithic (bit-for-bit, fp32), and the
    reduce-scatter/all-gather pair round-trips exactly."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.optim.buckets import (
        bucketed_all_gather, bucketed_all_reduce, bucketed_reduce_scatter,
        make_buckets,
    )
    from repro.optim.flat import flatten, make_layout

    mesh = jax.make_mesh((8,), ("data",))
    rng = np.random.default_rng(3)
    tree = {
        "w": rng.normal(size=(129, 31)).astype(np.float32),
        "b": rng.normal(size=(977,)).astype(np.float32),
        "k": rng.normal(size=(3, 3, 3)).astype(np.float32),
    }
    layout = make_layout(tree)
    buckets = make_buckets(layout, bucket_bytes=2048, n_shards=8)
    assert buckets.num_buckets > 1

    def worker(seed):
        g = flatten(layout, tree) * (1.0 + seed[0])
        mono = jax.lax.pmean(g, "data")
        buck = bucketed_all_reduce(g, buckets, "data", op="mean")
        rs = bucketed_reduce_scatter(g, buckets, "data", op="mean")
        ag = bucketed_all_gather(rs, buckets, "data")
        return mono, buck, ag

    fn = jax.jit(jax.shard_map(
        worker, mesh=mesh, in_specs=P("data"), out_specs=(P(), P(), P()),
        check_vma=False,
    ))
    mono, buck, ag = fn(np.arange(8.0, dtype=np.float32))
    assert bool(jnp.all(mono == buck)), "bucketed != monolithic (bitwise)"
    np.testing.assert_allclose(np.asarray(ag), np.asarray(mono), rtol=1e-6)


def check_flat_parity():
    """Faithful flat-engine training (bucketed all-reduce + fused flat
    Adam) and the ZeRO flat path must both track the legacy GSPMD adam
    step loss-for-loss."""
    import jax
    from repro.configs import get_smoke_config
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import _mk
    from repro.models.common import ShardRules
    from repro.optim import OptConfig
    from repro.train.loop import init_sharded
    from repro.train.step import TrainSettings, jit_train_step

    cfg = get_smoke_config("smollm-360m")
    mesh = _mk((8, 1), ("data", "model"))
    shape = ShapeConfig("t", "train", 8, 16)   # (seq_len, global_batch)
    opt = OptConfig(kind="adam", lr=1e-3, bucket_mb=0.05)
    rng = np.random.default_rng(11)
    tokens = rng.integers(0, cfg.vocab, size=(16, 9)).astype(np.int32)

    def run(settings, rules, steps=3):
        stepf, _, in_sh = jit_train_step(
            cfg, mesh, rules, opt, shape, settings, donate=False)
        params, opt_state = init_sharded(cfg, mesh, rules, opt, 0, settings)
        batch = {"tokens": jax.device_put(tokens, in_sh[2]["tokens"])}
        losses = []
        for _ in range(steps):
            params, opt_state, m = stepf(params, opt_state, batch)
            losses.append(float(m["loss"]))
        return stepf._flat_engine, losses

    rules_f = ShardRules.for_mesh(mesh, faithful=True)
    mode_flat, flat = run(TrainSettings(faithful=True), rules_f)
    assert mode_flat == "faithful", mode_flat
    mode_leg, legacy = run(
        TrainSettings(faithful=True, flat_engine="off"), rules_f)
    assert mode_leg is None
    np.testing.assert_allclose(flat, legacy, rtol=2e-3)

    mode_z, zero = run(TrainSettings(flat_engine="zero"),
                       ShardRules.for_mesh(mesh))
    assert mode_z == "zero", mode_z
    np.testing.assert_allclose(zero, flat, rtol=2e-3)


def check_collectives():
    import repro.core as synk

    synk.fork()
    rng = np.random.default_rng(2)
    w = rng.normal(size=(6,)).astype(np.float32)
    params = synk.distribute({"w": w})
    params = synk.set_value(params, 3, {"w": w * 9})
    red = synk.all_reduce(params, "avg")
    expect = (w * 7 + w * 9) / 8
    for r in (0, 3, 7):
        np.testing.assert_allclose(synk.get_value(red, r)["w"], expect, rtol=1e-5)
    bc = synk.broadcast(params, root=3)
    np.testing.assert_allclose(synk.get_value(bc, 5)["w"], w * 9, rtol=1e-6)
    np.testing.assert_allclose(synk.as_replicated(bc)["w"], w * 9, rtol=1e-6)
    sc = synk.scatter_shared({"d": np.arange(16.0, dtype=np.float32)})
    np.testing.assert_allclose(
        synk.get_value(sc, 2)["d"], np.array([4.0, 5.0]), rtol=0)
    s = synk.all_reduce(params, "sum")
    np.testing.assert_allclose(synk.get_value(s, 0)["w"], w * 7 + w * 9, rtol=1e-5)


def check_sgd_parity():
    """Paper Appendix A: multi-GPU SGD with all-reduce(avg) must equal the
    serial single-device program."""
    import jax
    import jax.numpy as jnp
    import repro.core as synk

    synk.fork()
    rng = np.random.default_rng(3)
    X = rng.normal(size=(64, 8)).astype(np.float32)
    Y = (X @ rng.normal(size=(8,)) + 0.1).astype(np.float32)
    w0 = rng.normal(size=(8,)).astype(np.float32)
    lr = 0.05

    def grad_fn(x, y, w):
        return jax.grad(lambda w: jnp.mean((x @ w - y) ** 2))(w)

    # serial reference
    w_ref = w0.copy()
    for _ in range(5):
        g = np.asarray(grad_fn(jnp.asarray(X), jnp.asarray(Y), jnp.asarray(w_ref)))
        w_ref = w_ref - lr * g

    # synk program: local grads per worker, all-reduce(avg), local update
    f = synk.function(grad_fn, [synk.Scatter(), synk.Scatter(), synk.Broadcast()],
                      synk.Reduce("mean"))
    w = w0.copy()
    for _ in range(5):
        g = np.asarray(f(X, Y, w))
        w = w - lr * g
    np.testing.assert_allclose(w, w_ref, rtol=1e-5)


def check_elastic():
    """Checkpoint written under dp=8 restores under dp=4 (elastic)."""
    import tempfile

    import jax
    from repro.checkpoint import CheckpointManager
    from repro.configs import get_smoke_config
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import _mk
    from repro.models.common import ShardRules
    from repro.optim import OptConfig
    from repro.train import LoopConfig, TrainSettings, train

    cfg = get_smoke_config("smollm-360m")
    shape = ShapeConfig("t", "train", 16, 8)
    opt = OptConfig(kind="adam", lr=1e-2)
    with tempfile.TemporaryDirectory() as d:
        mesh8 = _mk((8, 1), ("data", "model"))
        r8 = ShardRules.for_mesh(mesh8)
        res = train(cfg, shape, mesh8, r8, opt, TrainSettings(),
                    LoopConfig(steps=4, ckpt_every=4, ckpt_dir=d, log_every=0))
        mesh4 = _mk((4, 2), ("data", "model"))
        r4 = ShardRules.for_mesh(mesh4)
        res2 = train(cfg, shape, mesh4, r4, opt, TrainSettings(),
                     LoopConfig(steps=6, ckpt_every=6, ckpt_dir=d, log_every=0))
        assert np.isfinite(res2["final_loss"])
        assert res2["final_loss"] < res["final_loss"] + 1.0



def check_decode_seq_sharded():
    """The position-minor decode step with its cache sharded over the
    sequence (and the lanes over data) equals the unsharded step: the
    shards' partial softmaxes and the new token merge exactly."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.models.attention import (
        DecodeSharding, decode_attention_pos_minor)

    Hk, rep, D, S = 2, 3, 8, 32
    one = jax.make_mesh((1, 1, 1), ("pod", "data", "model"),
                        axis_types=(AxisType.Auto,) * 3,
                        devices=jax.devices()[:1])
    eight = jax.make_mesh((1, 2, 4), ("pod", "data", "model"),
                          axis_types=(AxisType.Auto,) * 3)
    rng = np.random.default_rng(0)
    for B, idx in ((2, np.array([0, S - 1])), (2, np.array([13, 20])),
                   (1, np.array(S - 9))):
        f = lambda *shape: rng.normal(size=shape).astype(np.float32)
        args = (f(B, Hk, rep, D), f(B, Hk, D, S), f(B, Hk, D, S),
                f(B, Hk, D), f(B, Hk, D), idx.astype(np.int32))

        def run(mesh):
            dec = DecodeSharding.choose(mesh, B)
            kv = NamedSharding(mesh, P(dec.batch_axes or None, None, None,
                                       dec.seq_axes or None))
            q, kc, vc, kn, vn, cur = args
            kc, vc = jax.device_put(kc, kv), jax.device_put(vc, kv)
            fn = jax.jit(lambda *a: decode_attention_pos_minor(
                *a, sharding=dec, window=11, softcap=20.0))
            return dec, [np.asarray(x) for x in fn(q, kc, vc, kn, vn, cur)]

        dec, got = run(eight)
        assert dec.seq_axes, dec
        _, want = run(one)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
        for g, w in zip(got[1:], want[1:]):
            np.testing.assert_array_equal(g, w)


def check_serve_dp_sharded():
    """The serve engine on an (8, 1) data mesh (lanes over data, the paged
    pool's block dim over data) emits the single-device engine's greedy
    tokens on a staggered stream: slotted, paged with preemption, and the
    hybrid family. After prebuild the stream builds nothing."""
    import dataclasses

    import jax

    from repro.configs import get_smoke_config
    from repro.launch.mesh import local_mesh, single_device_mesh
    from repro.models import registry
    from repro.models.common import ShardRules
    from repro.serve import EngineConfig, ServeEngine

    one, dp = single_device_mesh(), local_mesh()
    assert dp.shape["data"] == 8, dp.shape
    # 8 lanes of 32 at page 8 need up to 32 blocks; 16 forces preemption
    preempt = dict(kv_layout="paged", page_size=8, num_blocks=16,
                   admission="preempt")
    rng = np.random.default_rng(0)
    for arch, modes in (("smollm-360m", ({}, preempt)),
                        ("zamba2-1.2b", ({},))):
        cfg = dataclasses.replace(get_smoke_config(arch),
                                  compute_dtype="float32")
        params = registry.get_module(cfg).init(cfg, jax.random.PRNGKey(0))
        # more requests than lanes, so lanes are reused mid-stream
        prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
                   for n in rng.integers(2, 15, 19)]
        budgets = [int(b) for b in rng.integers(3, 12, 19)]

        def drive(mesh, **extra):
            eng = ServeEngine(cfg, mesh, ShardRules.for_mesh(mesh), params,
                              EngineConfig(max_slots=8, max_len=32, **extra))
            eng.prebuild()
            builds = eng.stats["builds"]
            rids = [eng.submit(p, max_new_tokens=b)
                    for p, b in zip(prompts, budgets)]
            while eng.has_work():
                eng.step()
                eng.check_invariants()
            assert eng.stats["builds"] == builds, (arch, extra)
            return [list(eng.completions[r].tokens) for r in rids], eng

        want, _ = drive(one)
        for extra in modes:
            got, eng = drive(dp, **extra)
            assert got == want, (arch, extra)
            if extra.get("admission") == "preempt":
                assert eng.counters["preemptions"] > 0
                assert eng.alloc.in_use == 0


CHECKS = {
    "scatter_reduce": check_scatter_reduce,
    "indexing": check_indexing,
    "indexing_global": check_indexing_global,
    "collectives": check_collectives,
    "sgd_parity": check_sgd_parity,
    "elastic": check_elastic,
    "bucketed_reduce": check_bucketed_reduce,
    "flat_parity": check_flat_parity,
    "decode_seq_sharded": check_decode_seq_sharded,
    "serve_dp_sharded": check_serve_dp_sharded,
}


def main():
    name = sys.argv[1] if len(sys.argv) > 1 else "all"
    names = list(CHECKS) if name == "all" else [name]
    for n in names:
        CHECKS[n]()
        print(f"[md_checks] {n} OK")


if __name__ == "__main__":
    main()
