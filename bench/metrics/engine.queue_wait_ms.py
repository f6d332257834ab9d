"""Mean of the requests' own ``queue_wait`` spans (serve/router.py,
serve/engine.py) in the traced window: from when a request entered the
router's queue to its admission to a lane."""


def read(rec: dict):
    d = (rec.get("spans") or {}).get("queue_wait")
    return 1e3 * sum(d) / len(d) if d else None
