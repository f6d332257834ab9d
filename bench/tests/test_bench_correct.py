"""What decides ``correct``: the harness driven end to end on the CPU at a
small size (the device check skipped), once sound and once with the
timed path broken underneath, for each fault a cell can have; and the
control, the reference in fp8, in the program's place.  The limits are
those set for this size in benchkit.py."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchkit import ROOT, tiny

from bench import harness
from bench.kinds import serve, train

SEED = 2**35 + 11


def run_cell(cell, tmp_path, seconds=0.3):
    out = cell.kind().run(cell, seed=SEED, seconds=seconds, trace=False,
                          t_start=0.0, devices=jax.devices()[:cell.chips],
                          scratch=tmp_path)
    line = harness.result_line(checks=out["checks"], attempted=out["attempted"],
                               failed=out["failed"], metrics={}, device={})
    return line


def test_train_sound_run_is_correct(tmp_path):
    line = run_cell(tiny("train-smollm-360m-dp1"), tmp_path)
    assert line["correct"], line["checks"]


def test_train_step_that_returns_its_state_unchanged(tmp_path, monkeypatch):
    def frozen(self, cell, devices, _init=train.Program.__init__):
        _init(self, cell, devices)
        self.step = lambda p, s, b: (p, s, {"loss": jnp.float32(5.0)})

    monkeypatch.setattr(train.Program, "__init__", frozen)
    line = run_cell(tiny("train-smollm-360m-dp1"), tmp_path)
    assert not line["correct"]
    assert line["checks"]["update"]["value"] == pytest.approx(1.0)


def test_train_half_the_batch_left_out(tmp_path, monkeypatch):
    from repro.models import lm

    real = lm.loss_fn

    def half(cfg, mesh, rules, params, batch, **kw):
        b = batch["tokens"].shape[0]
        return real(cfg, mesh, rules, params,
                    {"tokens": batch["tokens"][: b // 2]}, **kw)

    monkeypatch.setattr(lm, "loss_fn", half)
    line = run_cell(tiny("train-smollm-360m-dp1"), tmp_path)
    assert not line["correct"], line["checks"]


def test_train_fp8_control_fails():
    cell = tiny("train-smollm-360m-dp1")
    want = train.reference_readings(cell, SEED)
    got = train.reference_readings(cell, SEED, policy="fp8")
    vals = train.numbers(got, want)
    limits = cell.traffic["limits"]
    assert any(vals[k] > limits[k] for k in train.NUMBERS), vals


_EXCHANGE = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
sys.path.insert(0, sys.argv[1] + "/bench/tests")
import jax
from benchkit import tiny
from bench import harness
import repro.train.step as step

if sys.argv[2] == "broken":
    step.bucketed_all_reduce = lambda buf, buckets, axes, op="mean": buf
cell = tiny("train-smollm-360m-dp4")
out = cell.kind().run(cell, seed=7, seconds=0.3, trace=False, t_start=0.0,
                      devices=jax.devices()[:4], scratch=None)
print(json.dumps({c.name: [c.value, c.limit] for c in out["checks"]}))
"""


@pytest.mark.parametrize("mode", ["sound", "broken"])
def test_train_exchange_between_chips_left_out(mode):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, "-c", _EXCHANGE, str(ROOT), mode],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    checks = json.loads(out.stdout.strip().splitlines()[-1])
    ok = all(v <= lim for v, lim in checks.values())
    assert ok == (mode == "sound"), checks


def test_serve_sound_run_is_correct(tmp_path):
    line = run_cell(tiny("serve-smollm-360m-chat"), tmp_path, seconds=1.0)
    assert line["correct"], line["checks"]
    assert line["attempted"] == 20


def test_serve_token_altered_where_it_is_produced(tmp_path, monkeypatch):
    import repro.serve.step as sstep

    real = sstep.sample_tokens

    def altered(logits, *a, **kw):
        return (real(logits, *a, **kw) + 1) % logits.shape[-1]

    monkeypatch.setattr(sstep, "sample_tokens", altered)
    line = run_cell(tiny("serve-smollm-360m-chat"), tmp_path, seconds=1.0)
    assert not line["correct"], line["checks"]


def test_serve_fp8_control_fails():
    cell = tiny("serve-smollm-360m-chat")
    rng = np.random.default_rng(1)
    sample = [(rng.integers(0, 256, n, dtype=np.int32),
               rng.integers(0, 256, 20, dtype=np.int32)) for n in (5, 20, 30, 40)]
    gaps = serve.reference_gaps(cell, SEED, sample, control=True)
    assert gaps.size == 80
    assert gaps.max() > cell.traffic["limits"]["logit_gap"]
