"""AOT executable cache shared by the train and serve dispatch paths.

``SynkFunction`` (core/function.py) caches one ``.lower().compile()``'d
executable per call signature so steady-state dispatch is a dict probe.
The serve engine needs exactly the same machinery for its prefill/decode
executables — keyed on (config, bucketed prompt length, slot count)
instead of argument signatures — so the cache lives here as a small
reusable class instead of inline in ``SynkFunction.__call__``.

The cache is deliberately dumb: a dict from a hashable key to whatever
``build()`` returned, plus hit/miss counters.  Callers own key hygiene
(include every static option that changes the lowered program) and
eviction (none — executables are meant to live for the process; an
unbounded signature space is a caller bug, surfaced by ``builds``
growing without bound).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Iterator


class AotCache:
    """Keyed store of AOT-compiled executables with hit/miss counters.

    ``stats["builds"]`` counts cache misses (one trace+compile each);
    ``stats["cache_hits"]`` counts steady-state dispatches.  A warmed-up
    caller must show a flat ``builds`` counter — the tier-1 tests assert
    this for the serve engine and for ``SynkFunction``.

    Every miss also records its lower+compile wall seconds in
    ``build_seconds`` (always wall time, even when the owning engine runs
    on a fake clock — compile cost is a real-world budget), and emits an
    ``aot_build`` trace span when an ``obs`` handle is attached.

    Invariants: ``builds == len(self)`` (every miss stores exactly one
    entry, nothing is ever evicted); ``builds + cache_hits`` == total
    ``get`` calls; a key's entry is immutable once stored (``get`` never
    re-runs ``build`` for a present key, so sharing one cache across
    engines/benches can never recompile behind a caller's back).
    """

    def __init__(self, name: str = "aot", *, obs=None):
        self.name = name
        self._entries: dict[Any, Any] = {}
        self.stats = {"builds": 0, "cache_hits": 0}
        self.build_seconds: dict[Any, float] = {}
        self.obs = obs

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key) -> bool:
        return key in self._entries

    def keys(self) -> Iterator[Any]:
        return iter(self._entries)

    def get(self, key, build: Callable[[], Any]):
        """Return the cached entry for ``key``, building it on first use."""
        entry = self._entries.get(key)
        if entry is None:
            self.stats["builds"] += 1
            sid = None if self.obs is None else self.obs.begin(
                "aot_build", cat="aot", track=self.name, key=str(key))
            t0 = time.perf_counter()
            entry = build()
            self.build_seconds[key] = time.perf_counter() - t0
            if self.obs is not None:
                self.obs.end(sid)
            self._entries[key] = entry
        else:
            self.stats["cache_hits"] += 1
        return entry
