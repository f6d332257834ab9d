"""bench/metrics/flash_attention.step_ms.py on hand-made trace summaries:
it sums the flash kernels' instructions, per step, and reads None where
they did not run."""
from benchkit import ROOT

from bench import harness

READER = harness.load_module(ROOT / "bench/metrics/flash_attention.step_ms.py")


def _op(name, seconds, calls=1):
    text = f"%{name} = bf16[8,15,2048,64] custom-call(%a, %b, %c)"
    return {"s": seconds, "n": calls, "text": text}


def _record(ops, steps=3):
    return {"kind": "train", "steps": steps,
            "trace": {"ops": {k: _op(k, *v) for k, v in ops.items()}}}


def test_sums_the_flash_kernels_per_step():
    rec = _record({"flash_attention_fwd.1": (0.06, 64),
                   "flash_attention_fwd.2": (0.09, 32),
                   "flash_attention_bwd.1": (0.15, 32),
                   "fusion.12": (1.0,)})
    assert abs(READER.read(rec) - 100.0) < 1e-9


def test_none_without_the_kernels():
    assert READER.read(_record({"fusion.12": (1.0,)})) is None
    assert READER.read({"kind": "train", "steps": 3}) is None
    assert READER.read({"kind": "serve", "trace": {"ops": {}}}) is None
