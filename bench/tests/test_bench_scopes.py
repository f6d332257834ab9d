"""bench/scopes.py on the faithful train step compiled on the CPU (one
device, and four virtual ones), bench/attribute.py's gap labels on
hand-made events, and the readers of the engine's new spans on hand-made
records."""
import json
import os
import subprocess
import sys
import types

import pytest

from benchkit import ROOT

from bench import attribute, harness, scopes

# the named scopes of the step (train/step.py, models/lm.py), each of which
# must tag some instruction of the compiled module
WANT = {"forward", "backward", "flat_engine.flatten", "flat_engine.allreduce",
        "flat_engine.adam", "flat_engine.unflatten"}

_COMPILE = r"""
import collections, json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax
import numpy as np
from jax.sharding import AxisType, Mesh
from bench import scopes
from repro.configs import get_smoke_config
from repro.configs.base import ShapeConfig
from repro.models.common import ShardRules
from repro.optim import OptConfig
from repro.train.step import TrainSettings, jit_train_step

n = jax.device_count()
mesh = Mesh(np.array(jax.devices()).reshape(n, 1), ("data", "model"),
            axis_types=(AxisType.Auto,) * 2)
rules = ShardRules.for_mesh(mesh, faithful=True)
cfg = get_smoke_config("smollm-360m")
step, sds, _ = jit_train_step(
    cfg, mesh, rules, OptConfig(kind="adam", lr=1e-3, bucket_mb=0.05),
    ShapeConfig("t", "train", 32, 2 * n), TrainSettings(faithful=True))
smap = scopes.scope_map(step.lower(*sds).compile().as_text())
kinds = collections.Counter()
for name, (part, attn) in smap.items():
    kinds[part] += 1
    kinds["attention"] += attn
    if part.startswith("flat_engine.allreduce") and "all-reduce" in name:
        kinds["all-reduce"] += 1
print(json.dumps(kinds))
"""


@pytest.mark.parametrize("devices", [1, 4])
def test_every_scope_of_the_step_tags_instructions(devices):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    out = subprocess.run([sys.executable, "-c", _COMPILE, str(ROOT)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    kinds = json.loads(out.stdout.strip().splitlines()[-1])
    assert all(kinds.get(k, 0) > 0 for k in WANT | {"attention"}), kinds
    if devices > 1:     # the bucket collectives sit under their scope
        assert kinds.get("all-reduce", 0) > 0, kinds


HLO = """\
HloModule jit_step

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/transpose(jvp())/attention/mul"}
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1
  %dot.2 = f32[8]{0} dot(%p, %p), metadata={op_name="jit(step)/jvp()/attention/dot_general" source_line=3}
  %add.3 = f32[8]{0} add(%p, %p), metadata={op_name="jit(step)/jvp()/add"}
  %concatenate.4 = f32[16]{0} concatenate(%p, %p), metadata={op_name="jit(step)/flat_engine.flatten/concatenate"}
  %all-reduce.5 = f32[16]{0} all-reduce(%concatenate.4), metadata={op_name="jit(step)/flat_engine.allreduce/psum"}
  %copy.6 = f32[8]{0} copy(%p)
  ROOT %custom-call.7 = f32[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/flat_engine.adam/flat_adam"}
}
"""


def test_parts_from_hlo_text():
    smap = scopes.scope_map(HLO)
    assert smap["fusion.1"] == ("backward", True)       # its ROOT's op_name
    assert smap["dot.2"] == ("forward", True)
    assert smap["add.3"] == ("forward", False)
    assert smap["concatenate.4"] == ("flat_engine.flatten", False)
    assert smap["all-reduce.5"] == ("flat_engine.allreduce", False)
    assert smap["copy.6"] == ("unscoped", False)
    assert smap["custom-call.7"] == ("flat_engine.adam", False)


def test_seconds_by_part_over_a_trace_summary():
    smap = scopes.scope_map(HLO)
    summary = {"ops": {
        "%fusion.1 [fusion]": {"s": 0.5, "n": 1, "text": ""},
        "%dot.2 [dot]": {"s": 0.25, "n": 1, "text": ""},
        "%add.3 [add]": {"s": 0.125, "n": 1, "text": ""},
        "%copy.6 [copy]": {"s": 0.0625, "n": 1, "text": ""},
        "%copy.99 [copy]": {"s": 0.03125, "n": 1, "text": ""},   # not in module
    }}
    got = scopes.seconds_by_part(summary, smap)
    assert got["backward"] == 0.5 and got["forward"] == 0.375
    assert got["attention"] == 0.75
    assert got["unscoped"] == 0.09375
    assert sum(got[p] for p in scopes.PARTS) == 0.96875     # every op once


def _pd(device, host):
    def ev(s, d, name):
        return types.SimpleNamespace(start_ns=s, duration_ns=d, name=name)

    def plane(name, lines):
        return types.SimpleNamespace(name=name, lines=[
            types.SimpleNamespace(name=ln, events=[ev(*e) for e in evs])
            for ln, evs in lines])

    return types.SimpleNamespace(planes=[
        plane("/device:TPU:0", [("XLA Ops", device)]),
        plane("/host:CPU", [("python3", host)])])


def test_gaps_labelled_by_the_innermost_program_span():
    op = "%fusion.1 = f32[8]{0} fusion()"
    device = [(0, 100, op), (150, 50, op), (300, 100, op), (450, 50, op)]
    host = [(0, 500, "bench.step"),
            (90, 200, "repro.router.step"),
            (100, 180, "repro.engine.step"),
            (100, 60, "repro.engine.advance"),     # holds the gap 100..150
            (200, 90, "repro.decode"),
            (210, 70, "repro.decode.fetch")]       # holds the gap 200..300
    got = attribute.labelled_gaps(_pd(device, host))
    # 400..450 has no program span open: the benchmark's label alone
    # each with where it starts in its innermost span, and that span's length
    assert got["longest"] == [["bench.step/decode.fetch", 100e-9, -10e-9, 70e-9],
                              ["bench.step/engine.advance", 50e-9, 0.0, 60e-9],
                              ["bench.step", 50e-9]]
    assert got["idle_s_by_label"]["bench.step"] == 50e-9
    assert got["window_s"] == 500e-9


def test_gap_label_without_any_annotation():
    assert attribute.label([], [(0, 10, "repro.decode")], 0, 4) \
        == "(no annotation)/decode"
    assert attribute.label([(0, 10, "bench.step")], [], 0, 4) == "bench.step"


SERVE = {"kind": "serve", "spans": {
    "engine.step": [0.100, 0.110],
    "decode": [0.090, 0.095],
    "prefill_chunk": [0.010],
    "queue_wait": [0.040, 0.060, 0.020]}}


@pytest.mark.parametrize("name,want", [
    ("engine.schedule_ms", 1e3 * (0.210 - 0.185 - 0.010) / 2),
    ("engine.queue_wait_ms", 40.0),
])
def test_engine_span_readers(name, want):
    cell = harness.load_cell("serve-smollm-360m-chat")
    reader = cell.metric_reader(name)
    assert reader.read(SERVE) == pytest.approx(want)
    # off their cells, and on a program without these spans: nothing
    assert reader.read({"kind": "train", "steps": 3}) is None
    assert reader.read({"kind": "serve", "spans": {"decode": [0.1]}}) is None
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["workloads"] == ["serve-smollm-360m-chat"]
