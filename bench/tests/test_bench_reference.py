"""The plain reference against the program's forward and loss, at a small
size on the CPU, with the program computing in float32 so that the two
must agree to float32 rounding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchkit import tiny

from bench import compare, precision


@pytest.fixture(scope="module")
def setup():
    from repro.launch.mesh import single_device_mesh
    from repro.models import registry
    from repro.models.common import ShardRules

    cell = tiny("train-smollm-360m-dp1", compute_dtype="float32")
    ref, adapter = cell.ref(), cell.adapter()
    cfg = adapter.arch(cell.config)
    mesh = single_device_mesh()
    rules = ShardRules.for_mesh(mesh)
    w = ref.init(cell.config, jax.random.PRNGKey(3))
    params = adapter.to_program(w)
    want = registry.abstract_params(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
    tokens = np.random.default_rng(0).integers(0, cfg.vocab, (3, 17), np.int32)
    return cell, ref, cfg, mesh, rules, w, params, tokens


def test_loss_and_gradients_match_the_program(setup):
    cell, ref, cfg, mesh, rules, w, params, tokens = setup
    from repro.models import registry

    mod = registry.get_module(cfg)
    with jax.default_matmul_precision("highest"):
        (got, _), g_prog = jax.value_and_grad(
            lambda p: mod.loss_fn(cfg, mesh, rules, p, {"tokens": tokens},
                                  remat=False), has_aux=True)(params)
        want, g_ref = jax.value_and_grad(
            lambda w: ref.loss(cell.config, w, jnp.asarray(tokens)))(w)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    n_prog = compare.leaf_norms(cell.adapter().from_program(g_prog))
    n_ref = compare.leaf_norms(g_ref)
    assert compare.worst_leaf_gap(n_prog, n_ref)[0] < 1e-4


def test_logits_match_the_program(setup):
    cell, ref, cfg, mesh, rules, w, params, tokens = setup
    from repro.models import lm

    with jax.default_matmul_precision("highest"):
        hidden, _, _ = lm.forward(cfg, mesh, rules, params, tokens, remat=False)
        got = lm.unembed(cfg, rules, params, hidden)
        want = ref.logits(cell.config, w, jnp.asarray(tokens))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_fp8_control_differs_and_f32_does_not(setup):
    cell, ref, cfg, mesh, rules, w, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        f32 = ref.logits(cell.config, w, jnp.asarray(tokens), "f32")
        fp8 = ref.logits(cell.config, w, jnp.asarray(tokens), "fp8")
        g = jax.grad(lambda w: ref.loss(cell.config, w, jnp.asarray(tokens), "fp8"))(w)
    rel = float(jnp.max(jnp.abs(fp8 - f32)) / jnp.max(jnp.abs(f32)))
    assert 1e-3 < rel < 0.5
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(g))
    with pytest.raises(ValueError):
        precision.einsum("int4")
