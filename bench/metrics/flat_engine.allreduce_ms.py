"""Device time of the flat engine's bucketed gradient all-reduce per step
(optim/buckets.py): the self time of every ``all-reduce*`` operation in
the traced window, per chip, over the steps trained in it."""
from bench import trace_reduce


def is_all_reduce(text: str) -> bool:
    return trace_reduce.op_label(text).split("[")[-1].startswith("all-reduce")


def read(rec: dict):
    t = rec.get("trace")
    if rec.get("kind") != "train" or not t or rec["chips"] < 2 or not rec["steps"]:
        return None
    hit = trace_reduce.op_seconds(t, is_all_reduce)
    if hit is None:
        return None
    return 1e3 * hit[0] / rec["steps"]
