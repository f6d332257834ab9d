"""Mean of the engine's own ``prefill_chunk`` span (one lane's bucketed
prompt prefill, serve/engine.py) over the traced window."""


def read(rec: dict):
    d = (rec.get("spans") or {}).get("prefill_chunk")
    return 1e3 * sum(d) / len(d) if d else None
