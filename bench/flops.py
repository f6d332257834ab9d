"""Operations and bytes the benchmark credits the program with.

Kept with the benchmark so that no change to the program can change how
its work is counted.
"""
from __future__ import annotations


def param_count(shapes) -> int:
    """Parameters in a tree of shapes (anything with ``.shape``)."""
    import math

    import jax

    return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))


def train_flops_per_token(n_params: int, *, attn_layers: int, heads: int,
                          head_dim: int, seq: int) -> float:
    """Model FLOPs of one trained token, forward and backward, with no
    recomputation: 6 N for the parameters' matrix products (N counted from
    the parameter tree; a tied embedding counts once, as the output
    projection it is), plus each attention layer's score and value
    products, 12 x heads x head_dim x seq (the full square, as PaLM's
    appendix B counts it)."""
    return 6.0 * n_params + 12.0 * attn_layers * heads * head_dim * seq


# the fused flat Adam pass reads p, g, m, v and writes p, m, v: seven fp32
# words per element
FLAT_ADAM_BYTES_PER_ELEMENT = 7 * 4


def flat_adam_bytes(n: int) -> int:
    return FLAT_ADAM_BYTES_PER_ELEMENT * n
