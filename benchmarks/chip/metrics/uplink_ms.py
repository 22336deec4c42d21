"""Device milliseconds per round on chip 0 in the scope ``fed.uplink``: the
uplink transforms of ``RoundEngine._comm_step`` (shift:q8: quantizer scale,
dither, shift memory). See ``scopes.py``."""

import scopes

UNIT = "ms"


def read(ctx):
    return scopes.per_round_ms(ctx, "fed.uplink")
