"""Bytes and device time of the epoch's exchanges between chips.

Each round of the static epoch ships, from every chip to each of its
``p - 1`` peers, the rows of every fetched degree class at the class's
width, as many as the class's cap (the longest list any chip sends any
peer in any round), padding included: ``wire_bytes`` counts them from
the program's shapes (``ClassLayout.fetch``). The exchange runs as the
program's ``all-to-all`` ops; ``exchange_s`` reads them from the device
trace, each from its start to its done where the trace splits it.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

from benchmarks.chip.devtrace import union_ns

__all__ = ["wire_bytes", "exchange_s"]

A2A = "all-to-all"
ID_BYTES = 4
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")


def wire_bytes(p: int, n_rounds: int, fetch) -> int:
    """Bytes one chip sends off itself per epoch: ``n_rounds`` rounds to
    each of ``p - 1`` peers, for each fetched class ``(width, cap)``
    ``cap`` rows of ``width`` int32 ids."""
    return (int(n_rounds) * (int(p) - 1) * ID_BYTES
            * sum(int(w) * int(c) for w, c in fetch))


def _kind(name: str) -> Optional[str]:
    """``"op"``, ``"start"`` or ``"done"`` for an event of an all-to-all
    (by its HLO opcode; ops named after it, such as the reshape that
    feeds it, are not), else None."""
    head, _, rest = name.partition(" = ")
    m = _OPCODE.search(" " + rest) if rest else None
    opcode = m.group(1) if m else ""
    if opcode == A2A:
        return "op"
    if opcode in (A2A + "-start", A2A + "-done"):
        return opcode.rsplit("-", 1)[1]
    if opcode in ("async-start", "async-done") and A2A in name:
        return opcode.split("-")[1]
    return None


def exchange_s(trace) -> Optional[Dict[str, float]]:
    """Seconds of each device's exchanges in the window: the union of
    its all-to-all ops, each split op from its start's beginning to the
    end of the done that follows it. None where the trace shows no
    device or no exchange."""
    if trace is None or not trace.devices:
        return None
    out = {}
    for dev in trace.devices:
        spans, open_starts = [], []
        for e in sorted(trace.ops(dev), key=lambda e: e.start_ns):
            kind = _kind(e.name)
            if kind == "op":
                spans.append((e.start_ns, e.end_ns))
            elif kind == "start":
                open_starts.append(e.start_ns)
            elif kind == "done":
                s = open_starts.pop(0) if open_starts else e.start_ns
                spans.append((s, e.end_ns))
        clipped = [(max(s, trace.lo), min(e, trace.hi)) for s, e in spans]
        out[dev] = sum(e - s for s, e in union_ns(
            c for c in clipped if c[1] > c[0])) * 1e-9
    return out if any(out.values()) else None
