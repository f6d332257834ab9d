"""Model FLOP/s utilization of the train step over the traced window:
model FLOPs per token (bench/flops.py) x tokens trained in the window,
over the window's seconds, over chips x the chip's bf16 peak."""
from bench.peaks import peaks_for


def read(rec: dict):
    if rec.get("kind") != "train" or not rec.get("tokens"):
        return None
    peak = peaks_for(rec["device_kind"])["bf16_flops_per_s"]
    achieved = rec["tokens"] * rec["flops_per_token"] / rec["window_s"]
    return 100.0 * achieved / (rec["chips"] * peak)
