"""Matrix products of the plain references, in a named precision.

``f32``: float32 operands at ``Precision.HIGHEST`` (on a TPU a float32
product is otherwise made in bfloat16 passes).

``fp8``: the control, one step below the bfloat16 the configurations
compute in, by the usual fp8 training recipe: each operand scaled by its
own absolute maximum into float8 e4m3, each product's incoming gradient
scaled into float8 e5m2, products accumulated in float32.  The operands
are rounded to fp8 and multiplied exactly in float32, which gives what an
fp8 matrix unit with float32 accumulation gives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _fake_quant(x, dtype, top: float):
    amax = jnp.max(jnp.abs(x))
    scale = top / jnp.maximum(amax, 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def _e4m3(x):
    return _fake_quant(x, jnp.float8_e4m3fn, 448.0)


_e4m3.defvjp(lambda x: (_e4m3(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _grad_e5m2(x):
    return x


_grad_e5m2.defvjp(lambda x: (x, None),
                  lambda _, g: (_fake_quant(g, jnp.float8_e5m2, 57344.0),))


def einsum(policy: str):
    """``einsum(spec, a, b)`` in the given precision, float32 out."""
    def f32(spec, a, b):
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)

    if policy == "f32":
        return f32
    if policy == "fp8":
        return lambda spec, a, b: _grad_e5m2(
            f32(spec, _e4m3(a.astype(jnp.float32)), _e4m3(b.astype(jnp.float32))))
    raise ValueError(f"unknown precision {policy!r}")
