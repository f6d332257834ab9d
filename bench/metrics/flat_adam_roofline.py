"""The fused flat-Adam kernel's share of its roofline: the least time the
chip could take for the bytes it must move (bench/flops.py: p, g, m, v
read and p, m, v written, fp32, over the flat length), over the device
time of the kernel's events in the traced window.  The pass does one
multiply-add per byte or less, so HBM bandwidth bounds it.

The kernel carries no name of its own yet: it is the custom call whose
result is three fp32 buffers of the flat length."""
from bench import flops, trace_reduce
from bench.peaks import peaks_for


def is_flat_adam(text: str, n: int) -> bool:
    if "flat_adam" in text.partition(" = ")[0]:
        return True
    result, call, _ = text.partition(" custom-call(")
    return bool(call) and result.count(f"f32[{n}]") == 3


def read(rec: dict):
    t = rec.get("trace")
    if rec.get("kind") != "train" or not t:
        return None
    n = rec["flat_len"]
    hit = trace_reduce.op_seconds(t, lambda text: is_flat_adam(text, n))
    if hit is None or hit[0] <= 0:
        return None
    seconds, calls = hit
    bound = flops.flat_adam_bytes(n) / peaks_for(rec["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * bound * calls / seconds
