"""Device milliseconds per round on chip 0 in the scope ``fed.telemetry``:
the round's telemetry captures and ``Telemetry.finalize``, the sketch kernel
included. See ``scopes.py``."""

import scopes

UNIT = "ms"


def read(ctx):
    return scopes.per_round_ms(ctx, "fed.telemetry")
