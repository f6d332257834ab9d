"""Mean of the engine's own ``decode`` span (dispatch of one decode step
plus the fetch of its tokens, serve/engine.py) over the traced window."""


def read(rec: dict):
    d = (rec.get("spans") or {}).get("decode")
    return 1e3 * sum(d) / len(d) if d else None
