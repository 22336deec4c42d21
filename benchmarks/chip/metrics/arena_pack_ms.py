"""Device milliseconds per round on chip 0 in the scope ``fed.arena_pack``:
the pack of each gradient into the parameter arena (``RoundEngine._grad``).
See ``scopes.py``."""

import scopes

UNIT = "ms"


def read(ctx):
    return scopes.per_round_ms(ctx, "fed.arena_pack")
