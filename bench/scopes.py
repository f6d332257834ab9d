"""Which part of the train step each device operation belongs to, read
from the compiled module's HLO text (``compiled.as_text()``).

A profiler trace names each operation by its HLO instruction
(``%fusion.547 = ...``) and carries no metadata, so the named scopes of
the program (``jax.named_scope``) are read from the module instead: every
instruction there carries ``metadata={op_name="jit(step)/.../<scope>/..."}``
unless XLA made it (a copy, a bitcast).  A fusion without metadata of its
own takes that of the last instruction with one in the computation it
calls (the ROOT, which comes last, where it has one).

The parts, by op_name:

- ``flat_engine.<part>``: under the train step's ``flat_engine.flatten``,
  ``.allreduce``, ``.adam`` or ``.unflatten`` scope (train/step.py);
- ``backward``: under ``transpose(``, the transpose of the forward pass
  that ``jax.grad`` builds.  The forward recomputed for the backward pass
  under remat (``checkpoint/rematted_computation``) sits under it too, and
  counts as backward: it runs in the backward pass;
- ``forward``: every other operation that carries an op_name;
- ``unscoped``: no op_name at all.

``attention`` cuts across forward and backward: an operation is attention
when a component of its op_name is ``attention`` (models/lm.py).
"""
from __future__ import annotations

import re

FLAT_ENGINE = "flat_engine."
PARTS = ("forward", "backward", "flat_engine.flatten", "flat_engine.allreduce",
         "flat_engine.adam", "flat_engine.unflatten", "unscoped")

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) ")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name (without ``%``) -> op_name ('' where there is
    none), over every computation of the module."""
    own, calls, named = {}, {}, {}     # named: computation -> its op_name
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None and line.rstrip().endswith("{"):
                comp = c.group(1)
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op else ""
        call = _CALLS.search(line)
        if call is not None:
            calls[name] = call.group(1)
        if own[name]:
            named[comp] = own[name]
    out = dict(own)
    for name, callee in calls.items():
        if not out[name]:
            out[name] = named.get(callee, "")
    return out


def part(op_name: str) -> str:
    """The part of the step one op_name belongs to (see the module doc)."""
    if not op_name:
        return "unscoped"
    for comp in op_name.split("/"):
        if comp.startswith(FLAT_ENGINE):
            return comp
    return "backward" if "transpose(" in op_name else "forward"


def is_attention(op_name: str) -> bool:
    return "attention" in op_name.split("/")


def scope_map(hlo_text: str) -> dict[str, tuple[str, bool]]:
    """Instruction name -> (part, is attention)."""
    return {name: (part(op), is_attention(op))
            for name, op in op_names(hlo_text).items()}


def instruction(op_key: str) -> str:
    """The instruction name of a trace summary's operation key
    (``%name [opcode]``, bench/trace_reduce.py)."""
    return op_key.split(" ", 1)[0].lstrip("%")


def seconds_by_part(summary: dict, smap: dict) -> dict[str, float]:
    """Device self seconds per chip of each part, and of ``attention``,
    over a trace summary's operations (bench/trace_reduce.py).  An
    operation the module does not hold counts as unscoped."""
    out = dict.fromkeys(PARTS + ("attention",), 0.0)
    for key, op in summary["ops"].items():
        p, attn = smap.get(instruction(key), ("unscoped", False))
        out[p] += op["s"]
        if attn:
            out["attention"] += op["s"]
    return out
