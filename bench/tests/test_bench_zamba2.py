"""The Zamba2 cell on the CPU at a tiny size with the 7B's structure: the
plain reference against the program's loss and gradients, and the serve
harness driven end to end, sound, with the served token altered, and with
the fp8 control in the program's place."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchkit import TINY_TRAFFIC

from bench import harness
from bench.kinds import serve

CELL = "serve-zamba2-7b-longdoc"
SEED = 2**35 + 11
TINY_ZAMBA = dict(
    hidden_size=48, attention_hidden_size=96, attention_head_dim=24,
    num_attention_heads=4, num_key_value_heads=4, intermediate_size=96,
    ffn_hidden_size=96, adapter_rank=4, mamba_headdim=16, n_mamba_heads=6,
    mamba_d_state=16, chunk_size=8, num_hidden_layers=6,
    layers_block_type=["mamba", "mamba", "hybrid", "mamba", "hybrid", "mamba"],
    hybrid_layer_ids=[2, 4], vocab_size=256)


def tiny(**model):
    cell = harness.load_cell(CELL)
    cell.config.update(TINY_ZAMBA, **model)
    cell.traffic.update(TINY_TRAFFIC["serve"])
    return cell


def run_cell(cell, tmp_path):
    out = cell.kind().run(cell, seed=SEED, seconds=1.0, trace=False,
                          t_start=0.0, devices=jax.devices()[:1],
                          scratch=tmp_path)
    return harness.result_line(checks=out["checks"], attempted=out["attempted"],
                               failed=out["failed"], metrics={}, device={})


def test_loss_and_gradients_match_the_program():
    from repro.launch.mesh import single_device_mesh
    from repro.models import zamba
    from repro.models.common import ShardRules

    cell = tiny(compute_dtype="float32")
    ref, adapter = cell.ref(), cell.adapter()
    cfg = adapter.arch(cell.config)
    mesh = single_device_mesh()
    rules = ShardRules.for_mesh(mesh)
    w = ref.init(cell.config, jax.random.PRNGKey(3))
    tokens = np.random.default_rng(0).integers(0, cfg.vocab, (3, 17), np.int32)
    with jax.default_matmul_precision("highest"):
        (got, _), g_prog = jax.value_and_grad(
            lambda p: zamba.loss_fn(cfg, mesh, rules, p, {"tokens": tokens},
                                    remat=False),
            has_aux=True)(adapter.to_program(w))
        want, g_ref = jax.value_and_grad(
            lambda w: ref.loss(cell.config, w, jnp.asarray(tokens)))(w)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    g_prog = adapter.from_program(g_prog)
    assert jax.tree.structure(g_prog) == jax.tree.structure(g_ref)
    for a, b in zip(jax.tree.leaves(g_prog), jax.tree.leaves(g_ref)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * float(jnp.linalg.norm(b))


def test_serve_sound_run_is_correct(tmp_path):
    line = run_cell(tiny(), tmp_path)
    assert line["correct"], line["checks"]
    assert line["attempted"] == 20


def test_serve_token_altered_where_it_is_produced(tmp_path, monkeypatch):
    import repro.serve.step as sstep

    real = sstep.sample_tokens

    def altered(logits, *a, **kw):
        return (real(logits, *a, **kw) + 1) % logits.shape[-1]

    monkeypatch.setattr(sstep, "sample_tokens", altered)
    line = run_cell(tiny(), tmp_path)
    assert not line["correct"], line["checks"]


def test_serve_fp8_control_fails():
    cell = tiny()
    rng = np.random.default_rng(1)
    sample = [(rng.integers(0, 256, n, dtype=np.int32),
               rng.integers(0, 256, 20, dtype=np.int32)) for n in (5, 20, 30, 40)]
    gaps = serve.reference_gaps(cell, SEED, sample, control=True)
    assert gaps.size == 80
    assert gaps.max() > cell.traffic["limits"]["logit_gap"]


def test_serve_split_names_the_innermost_scope():
    from bench import serve_split

    assert serve_split.part("") == "unscoped"
    assert serve_split.part("jit(fn)/argmax") == "other"
    assert serve_split.part(
        "jit(fn)/while/body/zamba.mamba/zamba.ssd/dot_general") == "zamba.ssd"
    assert serve_split.part("jit(fn)/zamba.mamba/add") == "zamba.mamba"
    assert serve_split.part(
        "jit(fn)/zamba.shared_block/attention/exp") == "attention"
    assert serve_split.part("jit(fn)/zamba.shared_block/dot") == "zamba.shared_block"
