"""The engine's own host time per step, outside the device work it waits
for: over the traced window, the engine's ``engine.step`` spans less the
``decode`` (dispatch and token fetch) and ``prefill_chunk`` spans inside
them (serve/engine.py), over the number of steps.  Admission, scheduling
and the per-lane bookkeeping after the fetch make it up."""


def read(rec: dict):
    spans = rec.get("spans") or {}
    steps = spans.get("engine.step")
    if not steps:
        return None
    inner = sum(spans.get("decode", ())) + sum(spans.get("prefill_chunk", ()))
    return 1e3 * (sum(steps) - inner) / len(steps)
