"""Device milliseconds per round on chip 0 in which an all-reduce ran and
no other operation did: the part of the round's cross-chip reductions
(the clients' mean of the uplink messages, and the quantizer's scale) that
the chip waits for. Containers (``while``, ``conditional``, ``call``),
which the trace lists beside the ops they run, are left out (see
``scopes.py``)."""

import scopes
import tracefile

UNIT = "ms"


def exposed_ns(trace: tracefile.Trace, containers: set, chip: int = 0):
    """Nanoseconds of the window in which an all-reduce ran on ``chip``
    and no other leaf op did; None where no all-reduce ran."""
    lo, hi = trace.window
    reduce, other = [], []
    for op in trace.devices[chip]:
        name = tracefile.op_name(op[0])
        if name in containers:
            continue
        (reduce if "all-reduce" in name else other).append(op)
    if not reduce:
        return None
    busy = tracefile.union(other, lo, hi)
    exposed = 0
    for s, e in tracefile.union(reduce, lo, hi):
        covered = sum(max(0, min(e, b) - max(s, a)) for a, b in busy)
        exposed += (e - s) - covered
    return exposed


def read(ctx):
    kinds = scopes.scope_map(scopes.module_text(ctx["job"].compiled))
    containers = {k for k, v in kinds.items() if v == scopes.CONTAINER}
    ns = exposed_ns(ctx["trace"], containers)
    if ns is None:
        return None
    return 1e-6 * ns / ctx["rounds"]
