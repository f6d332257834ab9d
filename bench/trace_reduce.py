"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

The trace holds one plane per chip (``/device:TPU:<n>``) whose ``XLA Ops``
line carries every operation the chip ran, nested where an operation (a
``while`` loop) contains others, and a ``/host:CPU`` plane whose threads
carry the benchmark's own ``TraceAnnotation`` spans (``bench.*``) on the
same clock.  The window is the extent of those annotations.  (The trace's
host and device clocks differ by about a millisecond; a window lasts
seconds.)

- busy: the union of the chip's operation intervals inside the window,
  averaged over chips; idle share = 1 - busy / window.
- ops: per operation, the device seconds inside the window (self time:
  what its nested operations cover is theirs), averaged over chips, with
  the operation's HLO text to match on.
- breakdown: the operations that took most self time, and the longest
  idle gaps of the first chip, each labelled by the benchmark annotation
  open on the host at the gap's middle.
"""
from __future__ import annotations

import collections
import contextlib
import pathlib
import re

ANNOTATION = "bench."
_OPCODE = re.compile(r"\s([a-z][\w\-.]*)\(")


def profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # annotations only, no Python calls
    return opts


@contextlib.contextmanager
def profile(trace_dir):
    """Trace the enclosed window with the JAX profiler into ``trace_dir``."""
    import jax

    jax.profiler.start_trace(str(trace_dir), profiler_options=profile_options())
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def find_xplane(trace_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(trace_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_label(text: str) -> str:
    """``%name [opcode]`` from an operation's HLO text."""
    name, _, rest = text.partition(" = ")
    m = _OPCODE.search(" " + rest)
    return f"{name} [{m.group(1)}]" if m else name


def _self_times(events):
    """(start, end, text, self_ns) of nested events on one line."""
    out, stack = [], []
    for s, e, text in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            out.append(tuple(stack.pop()))
        item = [s, e, text, e - s]
        if stack:
            stack[-1][3] -= min(e, stack[-1][1]) - s
        stack.append(item)
    out.extend(tuple(x) for x in stack)
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce_xspace(pd, *, annotation: str = ANNOTATION, top: int = 10) -> dict:
    """The summary of one trace (``jax.profiler.ProfileData``)."""
    host = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in line.events if e.name.startswith(annotation)]
    if not host:
        raise ValueError(f"trace holds no {annotation}* annotation")
    t0, t1 = min(h[0] for h in host), max(h[1] for h in host)

    chips = []
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                evs = [(max(e.start_ns, t0), min(e.start_ns + e.duration_ns, t1),
                        e.name) for e in line.events]
                chips.append([ev for ev in evs if ev[1] > ev[0]])
    if not chips:
        raise ValueError("trace holds no device plane with XLA Ops")

    busy, ops, counts, text = [], collections.Counter(), collections.Counter(), {}
    gaps = []
    for i, evs in enumerate(chips):
        merged = _union((s, e) for s, e, _ in evs)
        busy.append(sum(e - s for s, e in merged))
        for s, e, t, self_ns in _self_times(evs):
            key = op_label(t)
            ops[key] += self_ns
            counts[key] += 1
            text.setdefault(key, t)
        if i == 0:
            edges = [t0] + [x for m in merged for x in m] + [t1]
            gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    n = len(chips)

    def label(s, e):
        mid = (s + e) / 2
        open_ = [h for h in host if h[0] <= mid < h[1]]
        return max(open_)[2] if open_ else "(no annotation)"

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "chips": n,
        "ops": {k: {"s": v / n / 1e9, "n": counts[k] / n, "text": text[k][:400]}
                for k, v in ops.items()},
        "breakdown": {
            "device_ops": [[k, v / n / 1e9] for k, v in ops.most_common(top)],
            "idle_gaps": [[label(s, e), (e - s) / 1e9] for s, e in longest],
        },
    }


def reduce_dir(trace_dir, **kw) -> dict:
    from jax.profiler import ProfileData

    return reduce_xspace(ProfileData.from_file(str(find_xplane(trace_dir))), **kw)


def op_seconds(summary: dict, match) -> tuple[float, float] | None:
    """(device seconds, calls) of the operations whose HLO text ``match``
    accepts, per chip; None when there is none."""
    hits = [v for v in summary["ops"].values() if match(v["text"])]
    if not hits:
        return None
    return sum(v["s"] for v in hits), sum(v["n"] for v in hits)


def host_spans(events, t0: float, t1: float) -> dict[str, list[float]]:
    """Durations (seconds) of the program's own tracer spans (obs/trace.py
    events: ``ph`` B/E pairs sharing ``sid``) that lie inside [t0, t1]."""
    opened, out = {}, collections.defaultdict(list)
    for ev in events:
        if ev.get("ph") == "B":
            opened[ev["sid"]] = ev
        elif ev.get("ph") == "E" and ev.get("sid") in opened:
            b = opened.pop(ev["sid"])
            if t0 <= b["ts"] and ev["ts"] <= t1:
                out[b["name"]].append(ev["ts"] - b["ts"])
    return dict(out)
