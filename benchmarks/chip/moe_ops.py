"""Device time of the expert layer's parts.

The program names the expert layer's parts with ``jax.named_scope``:
``moe.route`` (router, top-k, the sort of the slots, the gather of their
rows and the weighted combine) and ``moe.experts`` (the grouped SwiGLU
products). They reach the compiled program as each instruction's
``op_name`` metadata, read from ``scopes.module_text``. The TPU compiler
lowers each grouped product (``jax.lax.ragged_dot``) to kernels of its own
whose ``op_name`` it sets to ``ragged-dot-<kind>`` and which carry no
scope; those are the grouped products too. ``expert_ns`` sums chip 0's
device ops of the traced window by part, clipped to the window;
containers (``while``, ``conditional``, ``call``) are left out. A
program without the scopes gives nothing, and every metric read from
here then reports nothing.
"""

from __future__ import annotations

import re

import scopes
import tracefile

PARTS = ("moe.route", "moe.experts")
#: the op_name the TPU compiler gives the kernels of a grouped product
RAGGED_DOT = "ragged-dot"

_PART = re.compile(r"moe\.(?:route|experts)")


def part_map(hlo_text: str) -> dict:
    """``{"%instr": "moe.route" | "moe.experts" | None | scopes.CONTAINER}``
    for every instruction of an HLO module's text."""
    out = {}
    for name, rest in scopes._INSTR.findall(hlo_text):
        if scopes._CONTAINER_OP.search(rest.split(", metadata=", 1)[0]):
            out[name] = scopes.CONTAINER
            continue
        m = scopes._OP_NAME.search(rest)
        op_name = m.group(1) if m else ""
        found = _PART.findall(op_name)
        if found:
            out[name] = found[-1]
        elif op_name.startswith(RAGGED_DOT):
            out[name] = "moe.experts"
        else:
            out[name] = None
    return out


def expert_ns(ctx) -> dict | None:
    """``{part: ns}`` of chip 0's leaf ops in the window (read once per run
    and kept in ``ctx``), or None where no op of the window is a part."""
    if "moe_ns" not in ctx:
        parts = part_map(scopes.module_text(ctx["job"].compiled))
        tr = ctx["trace"]
        lo, hi = tr.window
        out: dict = {}
        for text, s, e in tr.devices[0]:
            part = parts.get(tracefile.op_name(text))
            s, e = max(s, lo), min(e, hi)
            if part in PARTS and e > s:
                out[part] = out.get(part, 0) + (e - s)
        ctx["moe_ns"] = out or None
    return ctx["moe_ns"]


def per_round_ms(ctx, part: str):
    """Chip 0's device milliseconds per round in ``part``."""
    by = expert_ns(ctx)
    if by is None:
        return None
    return 1e-6 * by.get(part, 0) / ctx["rounds"]
