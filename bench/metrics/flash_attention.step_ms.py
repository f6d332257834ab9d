"""Device time of the flash-attention kernels per trained step
(kernels/flash_attention): the self time of every operation whose
instruction is named after one of them (``flash_attention_fwd``, and the
backward's), per chip, over the steps trained in the traced window.
None where no such kernel ran."""
from bench import trace_reduce


def is_flash(text: str) -> bool:
    return "flash_attention" in text.partition(" = ")[0]


def read(rec: dict):
    t = rec.get("trace")
    if rec.get("kind") != "train" or not t or not rec["steps"]:
        return None
    hit = trace_reduce.op_seconds(t, is_flash)
    if hit is None:
        return None
    return 1e3 * hit[0] / rec["steps"]
