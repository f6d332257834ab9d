"""Validation of the trip-count-aware HLO cost analyzer against
``compiled.cost_analysis()`` on unrolled probes (where XLA's counts are
exact), plus collective wire-byte accounting on known programs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.analysis import (
    V5E, model_flops, peaks_for, roofline_terms,
)
from repro.roofline.hlo_cost import analyze

ONE_MM = 2 * 128 * 128 * 128


def _xla_cost(compiled) -> dict:
    """compiled.cost_analysis() returns a dict on new JAX, [dict] on old."""
    ca = compiled.cost_analysis()
    return ca[0] if isinstance(ca, (list, tuple)) else ca


def _probe(L, unroll):
    def f(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), None
        c, _ = jax.lax.scan(body, x, ws, unroll=unroll)
        return c
    xs = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((L, 128, 128), jnp.float32)
    return jax.jit(f).lower(xs, ws).compile()


@pytest.mark.parametrize("L", [2, 5, 8])
def test_rolled_scan_matches_unrolled_xla_counts(L):
    mine = analyze(_probe(L, 1).as_text())
    xla_unrolled = _xla_cost(_probe(L, L))["flops"]
    # dot flops must match exactly; elementwise accounting adds ~2%
    assert abs(mine.flops - xla_unrolled) / xla_unrolled < 0.05
    assert mine.flops >= L * ONE_MM


def test_nested_scan_trip_count_product():
    def g(x, ws):
        def outer(c, w):
            def inner(c2, _):
                return jnp.tanh(c2 @ w), None
            c, _ = jax.lax.scan(inner, c, None, length=3)
            return c, None
        c, _ = jax.lax.scan(outer, x, ws)
        return c
    xs = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((5, 128, 128), jnp.float32)
    hc = analyze(jax.jit(g).lower(xs, ws).compile().as_text())
    assert abs(hc.flops - 15 * ONE_MM) / (15 * ONE_MM) < 0.05


def test_xla_cost_analysis_undercounts_loops():
    """The reason hlo_cost exists: XLA counts while bodies once."""
    rolled = _probe(8, 1)
    assert _xla_cost(rolled)["flops"] < 2 * ONE_MM  # counted once


def test_collective_wire_bytes_all_reduce():
    import os
    # single-device: no collectives
    def f(x):
        return jnp.sum(x * 2.0)
    comp = jax.jit(f).lower(jax.ShapeDtypeStruct((64,), jnp.float32)).compile()
    hc = analyze(comp.as_text())
    assert hc.collective_summary()["total_wire_bytes"] == 0


def test_wire_byte_formulas():
    from repro.roofline.hlo_cost import _wire_bytes
    R, g = 1024, 8
    assert _wire_bytes("all-gather", R, g) == int(R * 7 / 8)
    assert _wire_bytes("all-reduce", R, g) == int(2 * R * 7 / 8)
    assert _wire_bytes("reduce-scatter", R, g) == R * 7
    assert _wire_bytes("collective-permute", R, g) == R
    assert _wire_bytes("all-reduce", R, 1) == 0


def test_model_flops_accounting():
    from repro.configs import get_config
    from repro.configs.base import SHAPES
    cfg = get_config("qwen3-moe-30b-a3b")
    mf_train = model_flops(cfg, SHAPES["train_4k"])
    mf_decode = model_flops(cfg, SHAPES["decode_32k"])
    # MoE: active params (top-8 of 128) << total
    assert cfg.n_params_active < 0.2 * cfg.n_params_dense
    assert mf_train == 6.0 * cfg.n_params_active * 256 * 4096
    assert mf_decode == 2.0 * cfg.n_params_active * 128


def test_roofline_terms_dominance():
    v5e = peaks_for(V5E)
    t = roofline_terms({"flops": 197e12, "bytes accessed": 1e9}, 0, n_chips=1,
                       peaks=v5e)
    assert t["dominant"] == "compute_s"
    assert t["compute_s"] == pytest.approx(1.0)
    t = roofline_terms({"flops": 1e9, "bytes accessed": 819e9}, 0, n_chips=1,
                       peaks=v5e)
    assert t["dominant"] == "memory_s"
    t = roofline_terms({"flops": 0, "bytes accessed": 0}, 50e9, n_chips=1,
                       peaks=v5e)
    assert t["dominant"] == "collective_s"
    assert t["bound_s"] == pytest.approx(1.0)


def test_peaks_for_unknown_device_raises():
    """The peak table is keyed by device kind; an unknown kind is an
    error, not a default."""
    assert peaks_for(V5E).flops == 197e12
    with pytest.raises(ValueError, match="no peaks"):
        peaks_for("cpu")
