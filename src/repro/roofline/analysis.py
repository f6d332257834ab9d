"""Roofline analysis from compiled XLA artifacts (no hardware needed).

Terms (per device, peaks from :data:`PEAKS` by ``device_kind``):
    compute    = HLO_FLOPs_per_device / peak_FLOPs
    memory     = HLO_bytes_per_device / HBM_bw
    collective = wire_bytes_per_device / ICI_bw

``cost_analysis()`` yields per-device FLOPs and bytes for the SPMD
partitioned module.  Collective wire bytes are parsed from the optimized
HLO: for each all-reduce / all-gather / reduce-scatter / all-to-all /
collective-permute, ring-algorithm wire volume per participant is derived
from the result shape and replica-group size.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

import numpy as np


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float                  # bf16 FLOP/s per chip
    hbm_bw: float                 # bytes/s per chip
    ici_bw: float                 # bytes/s per link (one link, conservatively)
    # fixed per-collective cost (launch + ring setup + per-hop latency) for
    # the bucket-size autotuner (optim/buckets.resolve_bucket_bytes)
    ici_latency_s: float


# Per-chip peaks keyed by ``jax.Device.device_kind``.  TPU v5e: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of
# interconnect = 4 links of 50 GB/s); the 2 us collective latency is a model
# assumption, not a measurement.
PEAKS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                             ici_latency_s=2e-6),
}
# v5e's device_kind; the chip the production dry-run (launch/dryrun.py)
# plans for
V5E = "TPU v5 lite"


def peaks_for(device_kind: str) -> ChipPeaks:
    """The peak table entry for ``device_kind``; an unknown kind raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks for device kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add its published numbers to "
            "repro.roofline.analysis.PEAKS") from None

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1,
    "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?P<type>\(?[^=]*?)\s*"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?P<variant>-start|-done)?\("
)
_SHAPE_RE = re.compile(r"(?P<dt>[a-z0-9]+)\[(?P<dims>[0-9,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


@dataclasses.dataclass
class CollectiveStats:
    op: str
    result_bytes: int
    group_size: int
    wire_bytes: int
    count: int = 1


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt = m.group("dt")
        if dt not in _DTYPE_BYTES:
            continue
        dims = m.group("dims")
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))      # [n_groups, group_size]<=[...]
    m = _GROUPS_EXPL_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def _wire_bytes(op: str, result_bytes: int, g: int) -> int:
    """Ring-algorithm wire volume per participant."""
    if g <= 1:
        return 0
    if op == "all-gather":
        return int(result_bytes * (g - 1) / g)
    if op == "reduce-scatter":
        return int(result_bytes * (g - 1))          # operand = g * result
    if op == "all-reduce":
        return int(2 * result_bytes * (g - 1) / g)
    if op == "all-to-all":
        return int(result_bytes * (g - 1) / g)
    if op == "collective-permute":
        return result_bytes
    return 0


def parse_collectives(hlo_text: str) -> list[CollectiveStats]:
    out: dict[tuple, CollectiveStats] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or m.group("variant") == "-done":
            continue
        op = m.group("op")
        rb = _shape_bytes(m.group("type"))
        g = _group_size(line)
        wb = _wire_bytes(op, rb, g)
        key = (op, rb, g)
        if key in out:
            out[key].count += 1
            out[key].wire_bytes += wb
        else:
            out[key] = CollectiveStats(op, rb, g, wb)
    return sorted(out.values(), key=lambda c: -c.wire_bytes)


def collective_summary(stats: list[CollectiveStats]) -> dict:
    total = sum(c.wire_bytes for c in stats)
    by_op: dict[str, int] = {}
    for c in stats:
        by_op[c.op] = by_op.get(c.op, 0) + c.wire_bytes
    return {"total_wire_bytes": total, "by_op": by_op,
            "n_collectives": sum(c.count for c in stats)}


# ---------------------------------------------------------------------------


def model_flops(cfg, shape) -> float:
    """6·N·D (dense) / 6·N_active·D (MoE) — the 'useful' FLOPs.

    Training counts fwd+bwd (6ND); inference counts forward only (2ND).
    D = tokens processed by the step.
    """
    n = cfg.n_params_active
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch


def roofline_terms(cost: dict, coll_total_bytes: int, *, n_chips: int,
                   peaks: ChipPeaks) -> dict:
    """cost: compiled.cost_analysis() of the per-device SPMD module."""
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    t_compute = flops_dev / peaks.flops
    t_memory = bytes_dev / peaks.hbm_bw
    t_coll = coll_total_bytes / peaks.ici_bw
    terms = {"compute_s": t_compute, "memory_s": t_memory, "collective_s": t_coll}
    dom = max(terms, key=terms.get)
    terms["dominant"] = dom
    terms["flops_per_device"] = flops_dev
    terms["bytes_per_device"] = bytes_dev
    terms["wire_bytes_per_device"] = float(coll_total_bytes)
    # roofline-optimal step time = max of the three (perfect overlap)
    terms["bound_s"] = max(t_compute, t_memory, t_coll)
    return terms


def summarize_cell(cfg, shape, cost: dict, mem, hlo_text: str, n_chips: int,
                   device_kind: str) -> dict:
    """Roofline summary for ``n_chips`` of ``device_kind``; FLOPs/bytes/
    collectives from the trip-count-aware static HLO analysis (hlo_cost.py —
    ``cost_analysis()`` counts while bodies once, so scans would be
    undercounted by their trip counts)."""
    from .hlo_cost import analyze

    peaks = peaks_for(device_kind)
    hc = analyze(hlo_text)
    csum = hc.collective_summary()
    exact = {"flops": hc.flops, "bytes accessed": hc.bytes}
    terms = roofline_terms(exact, csum["total_wire_bytes"], n_chips=n_chips,
                           peaks=peaks)
    terms["collective_s_bf16norm"] = \
        csum["total_wire_bytes_bf16norm"] / peaks.ici_bw
    mf = model_flops(cfg, shape)
    hlo_total = terms["flops_per_device"] * n_chips
    terms["model_flops_total"] = mf
    terms["useful_flops_ratio"] = mf / hlo_total if hlo_total else 0.0
    # roofline fraction: useful-FLOPs time / achievable bound
    t_useful = mf / n_chips / peaks.flops
    terms["roofline_fraction"] = t_useful / terms["bound_s"] if terms["bound_s"] else 0.0
    out = {
        "arch": cfg.name,
        "device_kind": device_kind,
        "shape": shape.name,
        "kind": shape.kind,
        "n_chips": n_chips,
        "terms": terms,
        "collectives": csum,
        "top_collectives": hc.top_collectives(8),
        "xla_cost_analysis": {
            "flops_body_once": float(cost.get("flops", 0.0)),
            "bytes_body_once": float(cost.get("bytes accessed", 0.0)),
        },
    }
    if mem is not None:
        out["memory"] = {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_estimate_bytes": mem.argument_size_in_bytes
            + mem.output_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        }
    return out
