"""Paper Fig. 2 + Fig. 3 analogue: data-parallel scaling of SGD training.

The paper times ResNet-50 SGD on 1..8 GPUs of a DGX-1 under (a) fixed
global batch 64 and (b) batch scaled 64 x #GPUs.  Here the same experiment
runs a conv-net Synkhronos program on N in {1,2,4,8} forced host devices
(one subprocess per N so the device count can differ), measuring per-call
wall time of the synk function.  On this 1-core container the measured
numbers show *overhead* scaling, not compute scaling, so the harness also
reports the DERIVED v5e roofline speedup for the same program (compute
term scales 1/N; all-reduce term from the gradient bytes at ICI bw) —
that derived column is the Fig. 3 analogue.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src"))

from repro.roofline import V5E, peaks_for  # noqa: E402

_WORKER = r"""
import os, sys, time, json
n = int(sys.argv[1]); batch_mode = sys.argv[2]
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
os.environ["JAX_PLATFORMS"] = "cpu"   # forced host devices: CPU only
import numpy as np
import jax, jax.numpy as jnp
import repro.core as synk
from repro.launch.cache import enable_compile_cache
enable_compile_cache()

synk.fork()
B = 64 if batch_mode == "fixed" else 64 * n
rng = np.random.default_rng(0)
X = rng.normal(size=(B, 3, 32, 32)).astype(np.float32)
Y = rng.integers(0, 10, size=(B,)).astype(np.int32)

def init():
    k = jax.random.PRNGKey(0)
    ks = jax.random.split(k, 4)
    return {
        "c1": jax.random.normal(ks[0], (16, 3, 3, 3)) * 0.1,
        "c2": jax.random.normal(ks[1], (32, 16, 3, 3)) * 0.1,
        "w": jax.random.normal(ks[2], (32 * 8 * 8, 10)) * 0.01,
    }

def model(p, x):
    x = jax.lax.conv_general_dilated(x, p["c1"], (1, 1), "SAME")
    x = jax.nn.relu(x)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 2, 2), (1, 1, 2, 2), "VALID")
    x = jax.lax.conv_general_dilated(x, p["c2"], (1, 1), "SAME")
    x = jax.nn.relu(x)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 2, 2), (1, 1, 2, 2), "VALID")
    x = x.reshape(x.shape[0], -1)
    return x @ p["w"]

def grad_fn(x, y, p):
    def loss(p):
        logits = model(p, x)
        onehot = jax.nn.one_hot(y, 10)
        return -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1))
    return jax.grad(loss)(p)

params = init()
f = synk.function(grad_fn, [synk.Scatter(), synk.Scatter(), synk.Broadcast()],
                  synk.Reduce("mean"))
g = f(X, Y, params)                       # compile + warm
jax.block_until_ready(jax.tree.leaves(g)[0])
t0 = time.perf_counter(); iters = 10
for _ in range(iters):
    g = f(X, Y, params)
jax.block_until_ready(jax.tree.leaves(g)[0])
dt = (time.perf_counter() - t0) / iters
n_params = sum(x.size for x in jax.tree.leaves(params))
print(json.dumps({"n": n, "mode": batch_mode, "sec_per_call": dt,
                  "batch": B, "n_params": int(n_params)}))
"""


def run(n: int, mode: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", _WORKER, str(n), mode],
                       capture_output=True, text=True, env=env, timeout=600)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-2000:])
    return json.loads(r.stdout.strip().splitlines()[-1])


def derived_speedup(n: int, mode: str, n_params: int) -> float:
    """v5e roofline model in the paper's regime (ResNet-50, batch 64:
    ~1.6e12 fwd+bwd FLOPs, 25.6M params): compute term scales with
    devices; ring all-reduce of the flat fp32 gradient at ICI bw; fixed
    per-call host overhead ~50us.  Mirrors paper Fig. 2/3 on v5e."""
    flops_1gpu = 3 * 8.2e9 * 64   # ResNet-50: 2x fwd flops x batch, fwd+bwd
    resnet_params = 25.6e6
    v5e = peaks_for(V5E)
    peak, ici = v5e.flops, v5e.ici_bw
    overlap = 0.9                 # grad all-reduce overlaps bwd compute
    batch_scale = 1.0 if mode == "fixed" else n
    t_comp = flops_1gpu * batch_scale / n / peak
    t_coll = 0.0 if n == 1 else \
        (1 - overlap) * 2 * 4 * resnet_params * (n - 1) / n / ici
    t_host = 50e-6
    t1 = flops_1gpu / peak + t_host
    return t1 * batch_scale / (t_comp + t_coll + t_host)


def main(emit) -> None:
    base = {}
    for mode in ("fixed", "scaled"):
        for n in (1, 2, 4, 8):
            r = run(n, mode)
            if n == 1:
                base[mode] = r["sec_per_call"]
            measured = base[mode] * (r["batch"] / 64) / r["sec_per_call"]
            der = derived_speedup(n, mode, r["n_params"])
            emit(f"fig23/{mode}/gpus={n}", r["sec_per_call"] * 1e6,
                 f"speedup_measured={measured:.2f}x;speedup_derived_v5e={der:.2f}x")


if __name__ == "__main__":
    main(lambda n, us, d: print(f"{n},{us:.1f},{d}"))
