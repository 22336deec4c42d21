"""Device milliseconds per round on chip 0 in the scope ``fed.aggregate``:
the clients' mean and ``server_aggregate`` in ``RoundEngine._comm_step``.
See ``scopes.py``."""

import scopes

UNIT = "ms"


def read(ctx):
    return scopes.per_round_ms(ctx, "fed.aggregate")
