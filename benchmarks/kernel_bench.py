"""Micro-benchmarks of the Pallas kernels (interpret mode off the chip —
timing there is indicative only; the derived column reports the v5e
roofline time for the same workload, which is what the kernel targets)."""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.roofline import V5E, peaks_for  # noqa: E402

_V5E = peaks_for(V5E)
PEAK, HBM = _V5E.flops, _V5E.hbm_bw


def _timeit(fn, *args, iters=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main(emit) -> None:
    enable_compile_cache()
    rng = np.random.default_rng(0)

    # flash attention: B1 S2048 H8 D128
    from repro.kernels import flash_attention
    B, S, H, Hk, D = 1, 2048, 8, 2, 128
    q = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, Hk, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, Hk, D)).astype(np.float32))
    dt = _timeit(lambda *a: flash_attention(*a, causal=True), q, k, v)
    flops = 2 * 2 * B * H * S * S * D / 2      # causal halves
    emit("kernel/flash_attention/2k", dt * 1e6,
         f"v5e_roofline_us={flops / PEAK * 1e6:.1f}")

    from repro.kernels import rmsnorm_op
    x = jnp.asarray(rng.normal(size=(8192, 1024)).astype(np.float32))
    g = jnp.asarray(rng.normal(size=(1024,)).astype(np.float32))
    dt = _timeit(rmsnorm_op, x, g)
    bytes_ = 2 * x.size * 4
    emit("kernel/rmsnorm/8192x1024", dt * 1e6,
         f"v5e_roofline_us={bytes_ / HBM * 1e6:.1f}")

    from repro.kernels import ssd_op
    Bs, Hs, T, P, G, N = 1, 4, 1024, 64, 1, 64
    xs = jnp.asarray(rng.normal(size=(Bs, Hs, T, P)).astype(np.float32))
    dts = jnp.asarray(rng.uniform(0.01, 0.1, size=(Bs, Hs, T)).astype(np.float32))
    A = jnp.asarray(-np.ones(Hs, np.float32))
    Bm = jnp.asarray(rng.normal(size=(Bs, G, T, N)).astype(np.float32))
    Cm = jnp.asarray(rng.normal(size=(Bs, G, T, N)).astype(np.float32))
    dt = _timeit(lambda *a: ssd_op(*a, chunk=128), xs, dts, A, Bm, Cm)
    chunk = 128
    flops = Bs * Hs * (T / chunk) * (2 * chunk * chunk * N + 2 * chunk * chunk * P
                                     + 4 * chunk * N * P)
    emit("kernel/ssd/1k", dt * 1e6, f"v5e_roofline_us={flops / PEAK * 1e6:.2f}")

    from repro.kernels import flat_adam_op
    n = 1 << 20
    p = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    gr = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    m = jnp.zeros(n, jnp.float32)
    vv = jnp.zeros(n, jnp.float32)
    step = jnp.int32(1)
    dt = _timeit(lambda *a: flat_adam_op(*a, lr=1e-3), p, gr, m, vv, step)
    bytes_ = 7 * n * 4
    emit("kernel/flat_adam/1M", dt * 1e6,
         f"v5e_roofline_us={bytes_ / HBM * 1e6:.1f}")


if __name__ == "__main__":
    main(lambda n, us, x: print(f"{n},{us:.1f},{x}"))
