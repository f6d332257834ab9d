"""The general traffic generators.  A traffic file (bench/traffic/*.json)
holds only parameters; these functions turn them and ``--seed`` into
inputs.  Sizes and gaps sit at fixed quantiles of their distributions, so
the seed changes which tokens, not how much work.
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator for ``(seed, *stream)``; any whole seed."""
    return np.random.default_rng(
        np.random.SeedSequence([seed & (2**64 - 1), *stream]))


def _grid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_sizes(n: int, median: float, sigma: float, lo: int,
                    hi: int) -> np.ndarray:
    """``n`` sizes at the quantiles (i + 0.5) / n of a log-normal, clipped."""
    z = np.array([_NORMAL.inv_cdf(q) for q in _grid(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(np.int64)


def poisson_gaps(n: int, span: float) -> np.ndarray:
    """``n`` exponential gaps at the quantiles (i + 0.5) / n, scaled to sum
    to ``span`` seconds: a Poisson process of rate n / span."""
    g = -np.log1p(-_grid(n))
    return g * (span / g.sum())


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def train_tokens(seed: int, step: int, rows: int, seq: int,
                 vocab: int) -> np.ndarray:
    """The rows of one step: ``(rows, seq + 1)`` int32 token ids, fresh for
    every (seed, step)."""
    return rng(seed, 1, step).integers(0, vocab, (rows, seq + 1),
                                       dtype=np.int32)


# ---------------------------------------------------------------------------
# serve (open loop)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Request:
    index: int
    due: float                 # seconds after the window's start (< 0: warm-up)
    prompt: np.ndarray         # int32 token ids
    max_new_tokens: int
    in_window: bool


def serve_requests(seed: int, traffic: dict, vocab: int,
                   seconds: float) -> list[Request]:
    """Open-loop arrivals in three phases: warm-up (``warmup_s`` before the
    window), the window (``seconds``), and arrivals that go on while the
    window's requests drain (``drain_s``).  Each phase holds
    ``rate x length`` requests with Poisson gaps; prompt and output
    lengths are log-normal.  Only window requests are measured.

    The schedule (the order of gaps, prompt and output lengths) comes from
    the traffic's own ``schedule_seed``, the token ids from ``seed``: no
    end-of-sequence token stops a request early, so every seed gets the
    same work at the same times, and a run's timing does not depend on
    its seed."""
    rate = float(traffic["rate_per_s"])
    phases = (("warmup", -float(traffic["warmup_s"]), float(traffic["warmup_s"])),
              ("window", 0.0, float(seconds)),
              ("drain", float(seconds), float(traffic["drain_s"])))
    p, o = traffic["prompt"], traffic["output"]
    out: list[Request] = []
    for k, (name, start, span) in enumerate(phases):
        n = max(1, round(rate * span))
        order = rng(int(traffic["schedule_seed"]), 2, k)
        gaps = order.permutation(poisson_gaps(n, span))
        due = start + np.cumsum(gaps) - gaps     # first one at the start
        plens = order.permutation(lognormal_sizes(
            n, p["median"], p["sigma"], p["min"], p["max"]))
        olens = order.permutation(lognormal_sizes(
            n, o["median"], o["sigma"], o["min"], o["max"]))
        tokens = rng(seed, 2, k)
        for i in range(n):
            out.append(Request(
                len(out), float(due[i]),
                tokens.integers(0, vocab, int(plens[i]), dtype=np.int32),
                int(olens[i]), name == "window"))
    return out
