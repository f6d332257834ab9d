"""Share of the traced window in which no operation ran on the device,
averaged over the chips (bench/trace_reduce.py), in a training cell."""


def read(rec: dict):
    t = rec.get("trace")
    if rec.get("kind") != "train" or not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
