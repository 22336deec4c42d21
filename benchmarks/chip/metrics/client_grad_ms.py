"""Device milliseconds per round on chip 0 in the scope ``fed.client_grad``:
the clients' vmapped forward/backward (``RoundEngine._grad``; the arena
unpack slices fuse into it). See ``scopes.py``."""

import scopes

UNIT = "ms"


def read(ctx):
    return scopes.per_round_ms(ctx, "fed.client_grad")
