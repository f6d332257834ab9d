"""Paper Table 1 analogue: decomposed profile of the training loop.

The paper profiles (with CUDA launch blocking): Total / Theano Function /
Shuffle / Straggler / All-Reduce.  The analogue decomposes a Synkhronos
training iteration on 8 forced host devices into: total, function
(compute), shuffle (input indexing), and gradient all-reduce — each timed
with blocking, mirroring the table rows.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = r"""
import os, sys, time, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"   # forced host devices: CPU only
import numpy as np
import jax, jax.numpy as jnp
import repro.core as synk
from repro.launch.cache import enable_compile_cache
enable_compile_cache()

synk.fork()
rng = np.random.default_rng(0)
N, D, B = 4096, 256, 512
X = synk.data(rng.normal(size=(N, D)).astype(np.float32))
Y = synk.data(rng.normal(size=(N,)).astype(np.float32))
w = rng.normal(size=(D,)).astype(np.float32) * 0.1

def grad_fn(x, y, w):
    return jax.grad(lambda w: jnp.mean((x @ w - y) ** 2))(w)

f = synk.function(grad_fn, [synk.Scatter(), synk.Scatter(), synk.Broadcast()],
                  synk.Reduce(None))          # keep per-worker grads
params = synk.distribute({"w": w})

def bench(fn, iters=20):
    fn(); fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(jax.tree.leaves(out)[0] if out is not None else ())
    return (time.perf_counter() - t0) / iters

idx = rng.permutation(N)[:B]
t_shuffle = bench(lambda: (np.random.default_rng(1).permutation(N)[:B], None)[1] or X.excerpt(idx))
t_fn = bench(lambda: f(X, Y, w, batch=idx))
t_ar = bench(lambda: synk.all_reduce(params, "avg").tree)
t_total = bench(lambda: synk.all_reduce(
    synk.LocalValues({"g": f(X, Y, w, batch=idx)[0]}), "avg").tree)
print(json.dumps({"total": t_total, "function": t_fn, "shuffle": t_shuffle,
                  "all_reduce": t_ar}))
"""


def main(emit) -> None:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", _WORKER],
                       capture_output=True, text=True, env=env, timeout=600)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-2000:])
    d = json.loads(r.stdout.strip().splitlines()[-1])
    total = d["total"]
    for row in ("total", "function", "shuffle", "all_reduce"):
        emit(f"table1/{row}", d[row] * 1e6,
             f"fraction_of_total={d[row] / total:.3f}")


if __name__ == "__main__":
    main(lambda n, us, x: print(f"{n},{us:.1f},{x}"))
