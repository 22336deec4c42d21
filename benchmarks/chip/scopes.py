"""Device time of the round program's layers, from the program's scopes.

The program names its layers with ``jax.named_scope`` (``fed.client_grad``,
``fed.arena_pack``, ...: ``repro.core.telemetry.SCOPES``), which reaches the
compiled program only as each instruction's ``op_name`` metadata. The
innermost ``fed.*`` scope of an instruction's ``op_name`` names its layer; a
fusion carries its root's. ``scope_map`` reads that from the compiled
module's text, and ``layer_ns`` sums chip 0's device ops of the traced
window by layer, clipped to the window. Containers (``while``,
``conditional``, ``call``), which the trace lists beside the ops they run,
are left out. A program without the scopes gives no layer, and every
metric read from here then reports nothing.
"""

from __future__ import annotations

import re

import tracefile

#: the program's scopes (``repro.core.telemetry.SCOPES``), named here so the
#: benchmark also reads a program that has none
SCOPES = ("fed.client_grad", "fed.arena_pack", "fed.local_update",
          "fed.uplink", "fed.aggregate", "fed.telemetry", "fed.round_loss")
#: the value ``scope_map`` gives an instruction that only runs others
CONTAINER = "container"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%\S+) = (.*)$", re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"fed\.[a-z_]+")
_CONTAINER_OP = re.compile(r"\s(?:while|conditional|call)\(")


def scope_map(hlo_text: str) -> dict:
    """``{"%instr": innermost fed.* scope, None, or CONTAINER}`` for every
    instruction of an HLO module's text."""
    out = {}
    for name, rest in _INSTR.findall(hlo_text):
        if _CONTAINER_OP.search(rest.split(", metadata=", 1)[0]):
            out[name] = CONTAINER
            continue
        m = _OP_NAME.search(rest)
        found = _SCOPE.findall(m.group(1)) if m else []
        out[name] = found[-1] if found else None
    return out


def module_text(compiled) -> str:
    """The compiled module's text with its ``op_name`` metadata: ``as_text``,
    or, where that leaves the metadata out, the executable's HLO modules
    printed with it."""
    text = compiled.as_text()
    if _OP_NAME.search(text):
        return text
    from jax._src.lib import _jax

    options = _jax.HloPrintOptions()
    options.print_metadata = True
    return "\n\n".join(m.to_string(options) for m in
                       compiled.runtime_executable().hlo_modules())


def layer_ns(trace: tracefile.Trace, scopes: dict, chip: int = 0) -> dict:
    """``{scope or None: ns}`` of ``chip``'s leaf ops in the window, each op
    clipped to it."""
    lo, hi = trace.window
    out: dict = {}
    for text, s, e in trace.devices[chip]:
        scope = scopes.get(tracefile.op_name(text))
        if scope == CONTAINER:
            continue
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out[scope] = out.get(scope, 0) + (e - s)
    return out


def layers(ctx) -> dict | None:
    """The run's ``layer_ns`` (read once per run and kept in ``ctx``), or
    None where no op of the window has a scope."""
    if "layer_ns" not in ctx:
        by = layer_ns(ctx["trace"], scope_map(module_text(ctx["job"].compiled)))
        ctx["layer_ns"] = by if any(k in SCOPES for k in by) else None
    return ctx["layer_ns"]


def per_round_ms(ctx, scope: str):
    """Chip 0's device milliseconds per round in ``scope``."""
    by = layers(ctx)
    if by is None:
        return None
    return 1e-6 * by.get(scope, 0) / ctx["rounds"]


def unscoped_share(ctx):
    """% of chip 0's leaf-op time in none of the scopes."""
    by = layers(ctx)
    if by is None:
        return None
    rest = sum(ns for scope, ns in by.items() if scope not in SCOPES)
    return 100.0 * rest / sum(by.values())
