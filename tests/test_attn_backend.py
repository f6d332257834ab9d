"""The Pallas attention backend is a drop-in for the XLA chunked path:
the full model loss and its gradients must agree between
attn_impl='chunked' and 'pallas' (kernels run in interpret mode on CPU),
and "auto" takes the kernels only where they apply."""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.kernels.flash_attention.ops import flash_applies
from repro.models import lm, registry
from repro.models.common import manual_mode


@pytest.mark.parametrize("arch", ["smollm-360m", "gemma2-27b"])
def test_pallas_backend_matches_chunked(arch, mesh, rules, key):
    base = dataclasses.replace(get_smoke_config(arch), compute_dtype="float32")
    mod = registry.get_module(base)
    params = mod.init(base, key)
    batch = {"tokens": jax.random.randint(key, (2, 33), 0, base.vocab)}

    losses = {}
    for impl in ("chunked", "pallas"):
        cfg = dataclasses.replace(base, attn_impl=impl)
        loss, _ = jax.jit(
            lambda p, b, c=cfg: registry.get_module(c).loss_fn(c, mesh, rules, p, b)
        )(params, batch)
        losses[impl] = float(loss)
    np.testing.assert_allclose(losses["pallas"], losses["chunked"],
                               rtol=2e-5, atol=2e-5)


def test_pallas_backend_trainable(mesh, rules, key):
    """The kernels' backward gives the model the gradients of the chunked
    path, leaf by leaf."""
    base = dataclasses.replace(get_smoke_config("smollm-360m"),
                               compute_dtype="float32")
    mod = registry.get_module(base)
    params = mod.init(base, key)
    batch = {"tokens": jax.random.randint(key, (2, 17), 0, base.vocab)}
    grads = {}
    for impl in ("chunked", "pallas"):
        cfg = dataclasses.replace(base, attn_impl=impl)
        grads[impl] = jax.jit(jax.grad(
            lambda p, b, c=cfg: mod.loss_fn(c, mesh, rules, p, b)[0]
        ))(params, batch)
    flat = {k: jax.tree_util.tree_leaves_with_path(g) for k, g in grads.items()}
    for (path, g), (_, want) in zip(flat["pallas"], flat["chunked"]):
        want = np.asarray(want)
        assert np.abs(want).max() > 0.0, path
        np.testing.assert_allclose(np.asarray(g), want, rtol=1e-4,
                                   atol=1e-4 * np.abs(want).max(),
                                   err_msg=jax.tree_util.keystr(path))


def _qk(S, *, H=15, Hk=5, D=64, B=8, Sk=None):
    q = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((B, Sk or S, Hk, D), jnp.bfloat16)
    return q, k


def test_auto_resolves_to_chunked_on_cpu(mesh):
    cfg = get_config("smollm-360m")
    assert cfg.attn_impl == "auto"
    assert lm._attn_impl(cfg, mesh, *_qk(2048)) == "chunked"


@pytest.fixture
def on_tpu(monkeypatch):
    """What the resolution sees on a TPU backend; nothing runs there."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("case,applies", [
    (dict(), True),                                  # the train cell's shape
    (dict(softcap=50.0), False),
    (dict(q_offset=128), False),
    (dict(kv_len=1000), False),
    (dict(S=2000), False),                           # does not tile
    (dict(S=1024, Sk=2048), False),                  # Sq != Sk
    (dict(causal=False), False),
    (dict(S=8192), False),                           # streams overflow VMEM
])
def test_flash_applies(on_tpu, case, applies):
    case = dict(case)
    q, k = _qk(case.pop("S", 2048), Sk=case.pop("Sk", None))
    kw = dict(causal=case.pop("causal", True), **case)
    assert flash_applies(q.shape, k.shape, q.dtype, **kw) is applies


def test_auto_takes_the_kernels_per_device(on_tpu, mesh):
    """A Pallas call has no partitioning rule: "auto" takes the kernels on
    a one-device mesh or inside a per-worker program, not under a
    partitioned many-device mesh; a softcap keeps the chunked path."""
    cfg = get_config("smollm-360m")
    q, k = _qk(2048)
    assert lm._attn_impl(cfg, mesh, q, k) == "pallas"
    many = types.SimpleNamespace(size=4)
    assert lm._attn_impl(cfg, many, q, k) == "chunked"
    with manual_mode():
        assert lm._attn_impl(cfg, many, q, k) == "pallas"
    capped = dataclasses.replace(cfg, attn_softcap=50.0)
    assert lm._attn_impl(capped, mesh, q, k) == "chunked"
    for impl in ("chunked", "pallas"):
        forced = dataclasses.replace(cfg, attn_impl=impl)
        assert lm._attn_impl(forced, many, q, k) == impl
